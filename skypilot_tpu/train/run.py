"""Training entrypoint: ``python -m skypilot_tpu.train.run``.

The runnable behind BASELINE.md configs #3 (multi-host FSDP finetune) and
#5 (preemptible pretrain with auto-recovery). One binary covers
single-chip, single-slice multi-host (``jax.distributed`` env injected by
the runtime agent, runtime/distributed_env.py), and checkpoint/resume
(Orbax into a mounted bucket — the managed-jobs recovery convention).

    python -m skypilot_tpu.train.run --model llama-350m --steps 100 \
        --batch 8 --seq 2048 --fsdp 8 --checkpoint-dir gs://bkt/ckpt

Data is synthetic-by-default (throughput/recovery benchmarking); a real
corpus plugs in by replacing ``synthetic_batch`` with a data iterator.
"""
from __future__ import annotations

import argparse
import collections
import json
import logging
import os
import sys
import time

logger = logging.getLogger(__name__)

MODELS = {
    'llama-tiny': ('llama', 'tiny'),
    'llama-350m': ('llama', 'bench_350m'),
    'llama-8b': ('llama', 'llama3_8b'),
    'llama-70b': ('llama', 'llama3_70b'),
    'moe-tiny': ('moe', 'tiny'),
    'moe-8x7b': ('moe', 'mixtral_8x7b'),
}


def main(argv=None) -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument('--model', default='llama-350m',
                        choices=sorted(MODELS))
    parser.add_argument('--steps', type=int, default=100)
    parser.add_argument('--batch', type=int, default=8,
                        help='Global batch size.')
    parser.add_argument('--seq', type=int, default=2048)
    parser.add_argument('--lr', type=float, default=3e-4)
    parser.add_argument('--dp', type=int, default=1)
    parser.add_argument('--fsdp', type=int, default=0,
                        help='0 = all remaining devices.')
    parser.add_argument('--tp', type=int, default=1)
    parser.add_argument('--checkpoint-dir', default=os.environ.get(
        'SKY_TPU_CHECKPOINT_DIR'))
    parser.add_argument('--checkpoint-every', type=int, default=50)
    parser.add_argument('--log-every', type=int, default=10)
    args = parser.parse_args(argv)
    logging.basicConfig(
        level=logging.INFO,
        format='%(asctime)s %(levelname)s %(name)s: %(message)s')

    import jax
    import jax.numpy as jnp

    from skypilot_tpu.infer import multihost
    from skypilot_tpu.models import llama
    from skypilot_tpu.ops import attention as attention_lib
    from skypilot_tpu.parallel import mesh as mesh_lib
    from skypilot_tpu.parallel import sharding as sharding_lib
    from skypilot_tpu.train import trainer
    from skypilot_tpu.utils import jax_env

    cache_dir = jax_env.attach_compile_cache()
    # Join the slice process group when the agent injected the env
    # (runtime/distributed_env.py); the one helper the server uses too.
    world = multihost.maybe_initialize_distributed()
    if world > 1:
        logger.info('jax.distributed up: process %d/%d',
                    jax.process_index(), world)

    family, preset = MODELS[args.model]
    if family != 'llama':
        raise SystemExit(f'--model {args.model}: the MoE trainer entry '
                         f'lands with the MoE train-step factory; use '
                         f'llama-* presets here for now')
    config = getattr(llama.LlamaConfig, preset)(max_seq_len=args.seq)

    device = jax_env.device_summary()
    n = device['count']
    fsdp = args.fsdp or n // (args.dp * args.tp)
    mesh = mesh_lib.make_mesh(dp=args.dp, fsdp=fsdp, tp=args.tp)
    logger.info(
        'platform=%s device_kind=%s devices=%d mesh dp=%d fsdp=%d tp=%d '
        'model=%s (%.0fM) attention=%s compile_cache=%s',
        device['platform'], device['device_kind'], n, args.dp, fsdp,
        args.tp, args.model, config.num_params / 1e6,
        attention_lib.resolve_impl(config.attention_impl, args.seq),
        cache_dir)

    # A run shorter than the warm-up would never leave it (bf16 params
    # round away updates that small, so the loss would not move).
    opt = trainer.make_optimizer(
        learning_rate=args.lr, total_steps=args.steps,
        warmup_steps=min(100, max(1, args.steps // 10)))
    step_fn = trainer.make_train_step(config, opt, mesh=mesh)

    start_step = 0
    if args.checkpoint_dir:
        from skypilot_tpu.train import checkpoint as ckpt_lib
        mgr = ckpt_lib.CheckpointManager(
            args.checkpoint_dir, save_interval_steps=args.checkpoint_every)
        state, restored = ckpt_lib.restore_or_init(
            args.checkpoint_dir,
            lambda: trainer.init_train_state(config, jax.random.PRNGKey(0),
                                             opt))
        if restored:
            start_step = int(state.step)
            logger.info('resumed from checkpoint at step %d', start_step)
    else:
        mgr = None
        state = trainer.init_train_state(config, jax.random.PRNGKey(0),
                                         opt)
    state = trainer.shard_train_state(state, mesh)

    batch = trainer.synthetic_batch(config, args.batch, args.seq,
                                    jax.random.PRNGKey(1))
    bshard = sharding_lib.batch_sharding(mesh)
    batch = {k: jax.device_put(v, bshard) for k, v in batch.items()}

    tokens_per_step = args.batch * args.seq
    t_last = time.perf_counter()
    for step in range(start_step, args.steps):
        state, metrics = step_fn(state, batch)
        if step == start_step:
            jax.block_until_ready(metrics['loss'])
            logger.info('first step (compile + run): %.1fs',
                        time.perf_counter() - t_last)
        if (step + 1) % args.log_every == 0 or step + 1 == args.steps:
            loss = float(metrics['loss'])
            dt = time.perf_counter() - t_last
            t_last = time.perf_counter()
            tps = tokens_per_step * args.log_every / dt
            logger.info('step %d/%d loss=%.4f tokens/s=%.0f',
                        step + 1, args.steps, loss, tps)
            if not jnp.isfinite(metrics['loss']):
                logger.error('non-finite loss; aborting')
                sys.exit(1)
        if mgr is not None:
            mgr.save(step + 1, jax.device_get(state))
    if mgr is not None:
        mgr.wait()
        mgr.close()
    # Placement, as the devices hold it: shards of the largest weight
    # per device and bytes in use (None where the backend keeps none).
    shards = collections.Counter(
        s.device.id for s in
        state.params['layers']['w_gate'].addressable_shards)
    logger.info('placement: %s', json.dumps({
        'w_gate_shards_per_device': dict(sorted(shards.items())),
        'bytes_in_use': jax_env.device_memory()}))
    logger.info('done: %d steps', args.steps)


if __name__ == '__main__':
    main()
