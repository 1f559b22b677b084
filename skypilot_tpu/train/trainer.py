"""Sharded training loop pieces: TrainState + jitted train step factory.

The compute-side counterpart of BASELINE.md's finetune configs. Everything
is mesh-agnostic: pass any Mesh (1 chip, v5e-8, v5p pod, or the CPU test
mesh) and the same code runs — the TPU-first property the whole framework
is built around.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from skypilot_tpu.models import llama
from skypilot_tpu.parallel import sharding as sharding_lib


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    step: jnp.ndarray
    params: Any
    opt_state: Any


def make_optimizer(learning_rate: float = 3e-4,
                   weight_decay: float = 0.1,
                   warmup_steps: int = 100,
                   total_steps: int = 10_000,
                   grad_clip: float = 1.0,
                   mu_dtype: Optional[str] = None
                   ) -> optax.GradientTransformation:
    """AdamW + cosine schedule. ``mu_dtype='bfloat16'`` halves the
    first-moment memory — the difference between fitting a ~1B model on
    one v5e chip and OOMing (nu stays fp32 for numerics)."""
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, learning_rate, warmup_steps, max(total_steps, warmup_steps + 1))
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(schedule, b1=0.9, b2=0.95, weight_decay=weight_decay,
                    mu_dtype=mu_dtype),
    )


def init_train_state(config: llama.LlamaConfig, key: jax.Array,
                     optimizer: optax.GradientTransformation) -> TrainState:
    params = llama.init_params(config, key)
    return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                      opt_state=optimizer.init(params))


def shard_train_state(state: TrainState, mesh: Mesh) -> TrainState:
    p_shard = sharding_lib.param_shardings(mesh, state.params)
    o_shard = sharding_lib.opt_state_shardings(mesh, state.opt_state,
                                               state.params)
    return TrainState(
        step=jax.device_put(state.step, NamedSharding(mesh, P())),
        params=sharding_lib.shard_pytree(state.params, p_shard),
        opt_state=sharding_lib.shard_pytree(state.opt_state, o_shard))


def make_train_step(config: llama.LlamaConfig,
                    optimizer: optax.GradientTransformation,
                    mesh: Optional[Mesh] = None):
    """Returns jitted (state, batch) -> (state, metrics).

    batch: {'tokens': [b, s] int32, 'targets': [b, s] int32,
            'mask': optional [b, s]}.
    Under a mesh, inputs/outputs carry NamedShardings and the state buffer
    is donated (in-place update on device).
    """

    def step_fn(state: TrainState,
                batch: Dict[str, jnp.ndarray]
                ) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
        def loss(params):
            return llama.loss_fn(config, params, batch['tokens'],
                                 batch['targets'], batch.get('mask'))

        loss_val, grads = jax.value_and_grad(loss)(state.params)
        updates, new_opt = optimizer.update(grads, state.opt_state,
                                            state.params)
        new_params = optax.apply_updates(state.params, updates)
        metrics = {
            'loss': loss_val,
            'grad_norm': optax.global_norm(grads),
            'step': state.step + 1,
        }
        return TrainState(step=state.step + 1, params=new_params,
                          opt_state=new_opt), metrics

    if mesh is None:
        return jax.jit(step_fn, donate_argnums=(0,))

    # Explicit shardings: params/opt as the rules say, batch over data axes,
    # metrics replicated.
    dummy_params_struct = jax.eval_shape(
        lambda: llama.init_params(config, jax.random.PRNGKey(0)))
    p_shard = sharding_lib.param_shardings(mesh, dummy_params_struct)
    o_struct = jax.eval_shape(lambda: optimizer.init(
        jax.tree_util.tree_map(jnp.zeros_like, dummy_params_struct)))
    o_shard = sharding_lib.opt_state_shardings(mesh, o_struct,
                                               dummy_params_struct)
    repl = NamedSharding(mesh, P())
    state_shard = TrainState(step=repl, params=p_shard, opt_state=o_shard)
    batch_shard = sharding_lib.batch_sharding(mesh)
    jitted = jax.jit(
        step_fn,
        in_shardings=(state_shard,
                      {'tokens': batch_shard, 'targets': batch_shard}),
        out_shardings=(state_shard,
                       {'loss': repl, 'grad_norm': repl, 'step': repl}),
        donate_argnums=(0,))

    def sharded_step(state, batch):
        # The mesh is ambient while the step traces, so code that must
        # run per shard (the Pallas attention kernels) can see it.
        with jax.set_mesh(mesh):
            return jitted(state, batch)
    return sharded_step


def synthetic_batch(config: llama.LlamaConfig, batch_size: int,
                    seq_len: int, key: jax.Array) -> Dict[str, jnp.ndarray]:
    tokens = jax.random.randint(key, (batch_size, seq_len + 1), 0,
                                config.vocab_size, dtype=jnp.int32)
    return {'tokens': tokens[:, :-1], 'targets': tokens[:, 1:]}
