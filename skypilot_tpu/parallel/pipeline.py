"""Pipeline parallelism: GPipe-style microbatch pipeline over a mesh axis.

The reference has no model parallelism of its own (SURVEY.md §2.8 —
delegated to torchrun/DeepSpeed in example YAMLs); this is the TPU-native
construction: stages are layer groups sharded over the ``pp`` mesh axis,
activations flow stage-to-stage via ``lax.ppermute`` inside ``shard_map``,
and the schedule is a single ``lax.scan`` over M + P - 1 ticks (the
pipeline bubble). **The backward pipeline comes from AD**: ppermute's
transpose is the reverse permute, so ``jax.grad`` of this forward IS the
reverse-schedule backward — no hand-written schedule.

Composes with the other axes: params stay fsdp/tp-sharded inside a stage;
``pp`` only partitions the layer axis.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from skypilot_tpu.models import llama
from skypilot_tpu.ops import norms
from skypilot_tpu.ops import rope as rope_lib


def pipeline_stages(stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
                    local_params: Any, microbatches: jnp.ndarray,
                    axis_name: str = 'pp') -> jnp.ndarray:
    """Run microbatches through all pipeline stages. CALL INSIDE shard_map.

    stage_fn(local_params, x) -> y: this stage's compute (same shape).
    microbatches: [M, ...] — every stage sees the full microbatch list;
    stage 0 injects them, later stages consume ppermuted activations.
    Returns [M, ...] stage outputs — valid on the LAST stage, zeros
    elsewhere (psum over ``axis_name`` broadcasts, since others are 0).
    """
    num_stages = jax.lax.axis_size(axis_name)
    stage = jax.lax.axis_index(axis_name)
    M = microbatches.shape[0]
    T = M + num_stages - 1
    shift = [(i, i + 1) for i in range(num_stages - 1)]

    def tick(carry, t):
        state, outputs = carry
        mb = jax.lax.dynamic_index_in_dim(
            microbatches, jnp.clip(t, 0, M - 1), 0, keepdims=False)
        x = jnp.where(stage == 0, mb, state)
        y = stage_fn(local_params, x)
        out_idx = t - (num_stages - 1)
        ci = jnp.clip(out_idx, 0, M - 1)
        valid = ((stage == num_stages - 1) & (out_idx >= 0)
                 & (out_idx < M))
        prev = jax.lax.dynamic_index_in_dim(outputs, ci, 0,
                                            keepdims=False)
        outputs = jax.lax.dynamic_update_index_in_dim(
            outputs, jnp.where(valid, y, prev), ci, 0)
        state = jax.lax.ppermute(y, axis_name, shift) \
            if num_stages > 1 else y
        return (state, outputs), None

    state0 = jnp.zeros_like(microbatches[0])
    out0 = jnp.zeros_like(microbatches)
    (_, outputs), _ = jax.lax.scan(tick, (state0, out0),
                                   jnp.arange(T))
    return outputs


def _llama_stage(config: llama.LlamaConfig, local_layers: Any,
                 x: jnp.ndarray, cos: jnp.ndarray,
                 sin: jnp.ndarray) -> jnp.ndarray:
    """One stage = scan over this stage's contiguous layer group."""
    def body(h, layer):
        fn = llama._layer  # noqa: SLF001 — same model family
        if config.remat:
            fn = jax.checkpoint(fn, static_argnums=(0,))
        return fn(config, h, layer, cos, sin, None), None
    x, _ = jax.lax.scan(body, x, local_layers)
    return x


def llama_pp_loss_fn(config: llama.LlamaConfig, mesh: Mesh,
                     num_microbatches: int,
                     dp_axis: Optional[str] = 'dp',
                     pp_axis: str = 'pp') -> Callable:
    """Build loss(params, tokens, targets) pipelined over ``pp_axis``.

    Layer-stacked params are split over stages (n_layers % pp == 0);
    embed/head/norms are computed on every stage (replicated compute —
    negligible next to the layer stack). Batch shards over ``dp_axis``.
    """
    pp = mesh.shape[pp_axis]
    if config.n_layers % pp != 0:
        raise ValueError(f'n_layers={config.n_layers} not divisible by '
                         f'pp={pp}')
    has_dp = dp_axis is not None and dp_axis in mesh.shape
    batch_spec = P(dp_axis) if has_dp else P()

    layer_specs = jax.tree_util.tree_map(
        lambda _: P(pp_axis), llama.LLAMA_LAYER_TREE)
    param_specs = {
        'embed': P(), 'layers': layer_specs, 'final_norm': P(),
        'lm_head': P(),
    }

    def inner(params, tokens, targets):
        cos, sin = rope_lib.rope_frequencies(config.head_dim,
                                             config.max_seq_len,
                                             config.rope_theta)
        b = tokens.shape[0]
        if b % num_microbatches != 0:
            raise ValueError(f'per-dp batch {b} not divisible by '
                             f'M={num_microbatches}')
        x = params['embed'][tokens]                 # [b, s, d]
        mbs = x.reshape(num_microbatches, b // num_microbatches,
                        *x.shape[1:])
        stage_fn = functools.partial(_llama_stage, config)
        outputs = pipeline_stages(
            lambda lp, h: stage_fn(lp, h, cos, sin),
            params['layers'], mbs, axis_name=pp_axis)
        # Valid only on the last stage; zeros elsewhere → psum broadcasts.
        outputs = jax.lax.psum(outputs, pp_axis)
        h = outputs.reshape(b, *outputs.shape[2:])
        h = norms.rms_norm(h, params['final_norm'], config.norm_eps)
        logits = (h @ params['lm_head']).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None],
                                   axis=-1)[..., 0]
        loss = jnp.mean(nll)
        if has_dp:
            loss = jax.lax.pmean(loss, dp_axis)
        return loss

    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(param_specs, batch_spec, batch_spec),
        out_specs=P(),
        check_vma=False)
