"""Mixture-of-Experts transformer (Mixtral-style): a TRAINING forward
that no normal path reaches.

Neither ``train/run.py`` (its ``moe-*`` entries exit) nor the serving
path runs this file. What is SERVED is another layer:
``ops/moe_dropless.py`` under ``models/nemotron_h.py`` (dropless,
sort / gather grouped products, told which experts the chip holds).
This file's dispatch drops the tokens past ``capacity`` and builds
``[T, E, C]`` tensors that grow with ``T**2``; a served answer may not
depend on a capacity, so it is not the layer to serve with.

Absent from the reference (SURVEY.md §2.8: EP delegated to user
frameworks); as built here:

- **GShard-style fixed-capacity dispatch**: routing produces dense
  dispatch/combine tensors, and expert compute is batched einsums over
  ``[experts, capacity, dim]`` — static shapes, MXU-shaped, no gather
  loops.
- **Expert parallelism, here, is a sharding annotation**:
  expert-stacked weights carry ``P('ep')`` on the expert axis, and under
  jit the dispatch/combine einsums would lower to all-to-alls over the
  ``ep`` mesh axis. No cell or chip run has shown it. The served layer
  takes the other road: expert parallelism as the chip's share
  (``experts_held`` / ``expert_offset``), code that knows which experts
  it holds; experts over more than one chip are not built (ROADMAP X1).
- Attention/norms/RoPE are shared with ``models/llama.py`` (same layer
  fn); only the MLP is replaced by the routed expert MLP.
- Router aux losses: load-balancing (Switch-style) + router z-loss,
  returned separately so the trainer can weight them.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from skypilot_tpu.models import llama
from skypilot_tpu.ops import norms
from skypilot_tpu.ops import rope as rope_lib

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    vocab_size: int = 32_000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14_336          # per-expert hidden dim
    n_experts: int = 8
    experts_per_token: int = 2     # top-k routing
    capacity_factor: float = 1.25  # expert capacity vs perfect balance
    max_seq_len: int = 8192
    rope_theta: float = 500_000.0
    norm_eps: float = 1e-5
    dtype: str = 'bfloat16'
    attention_impl: str = 'auto'
    remat: bool = True

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def num_params(self) -> int:
        d, f, v = self.dim, self.ffn_dim, self.vocab_size
        per_layer = (d * self.n_heads * self.head_dim
                     + 2 * d * self.n_kv_heads * self.head_dim
                     + self.n_heads * self.head_dim * d
                     + self.n_experts * 3 * d * f
                     + d * self.n_experts      # router
                     + 2 * d)
        return self.n_layers * per_layer + 2 * v * d + d

    @staticmethod
    def mixtral_8x7b(**kw) -> 'MoEConfig':
        return MoEConfig(**kw)

    @staticmethod
    def tiny(**kw) -> 'MoEConfig':
        base = dict(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                    n_kv_heads=2, ffn_dim=96, n_experts=4,
                    experts_per_token=2, max_seq_len=128,
                    dtype='float32')
        base.update(kw)
        return MoEConfig(**base)

    def as_llama(self) -> llama.LlamaConfig:
        """Attention-relevant view for reusing llama layer pieces."""
        return llama.LlamaConfig(
            vocab_size=self.vocab_size, dim=self.dim,
            n_layers=self.n_layers, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, ffn_dim=self.ffn_dim,
            max_seq_len=self.max_seq_len, rope_theta=self.rope_theta,
            norm_eps=self.norm_eps, dtype=self.dtype,
            attention_impl=self.attention_impl, remat=self.remat)


# Tree skeleton for sharding specs (see llama.LLAMA_LAYER_TREE).
MOE_LAYER_TREE: Dict[str, int] = {
    'attn_norm': 0, 'wq': 0, 'wk': 0, 'wv': 0, 'wo': 0,
    'mlp_norm': 0, 'router': 0, 'w_gate': 0, 'w_up': 0, 'w_down': 0,
}


def init_params(config: MoEConfig, key: jax.Array) -> Params:
    dtype = jnp.dtype(config.dtype)
    d, hd, f = config.dim, config.head_dim, config.ffn_dim
    L, E = config.n_layers, config.n_experts
    k_embed, k_layers, k_head = jax.random.split(key, 3)

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32)
                * scale).astype(dtype)

    ks = jax.random.split(k_layers, 8)
    scale = d ** -0.5
    out_scale = scale / (2 * L) ** 0.5
    layers = {
        'attn_norm': jnp.ones((L, d), dtype),
        'wq': normal(ks[0], (L, d, config.n_heads * hd), scale),
        'wk': normal(ks[1], (L, d, config.n_kv_heads * hd), scale),
        'wv': normal(ks[2], (L, d, config.n_kv_heads * hd), scale),
        'wo': normal(ks[3], (L, config.n_heads * hd, d), out_scale),
        'mlp_norm': jnp.ones((L, d), dtype),
        # Router in fp32: routing logits are precision-sensitive.
        'router': jax.random.normal(ks[4], (L, d, E),
                                    jnp.float32) * scale,
        'w_gate': normal(ks[5], (L, E, d, f), scale),
        'w_up': normal(ks[6], (L, E, d, f), scale),
        'w_down': normal(ks[7], (L, E, f, d), out_scale),
    }
    return {
        'embed': normal(k_embed, (config.vocab_size, d), 1.0),
        'layers': layers,
        'final_norm': jnp.ones((d,), dtype),
        'lm_head': normal(k_head, (d, config.vocab_size), scale),
    }


def _route(config: MoEConfig, h: jnp.ndarray, router_w: jnp.ndarray,
           capacity: int) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-k routing with fixed capacity.

    h: [T, d] tokens. Returns (dispatch [T, E, C] one-hot-ish fp,
    combine [T, E, C] gate-weighted, aux metrics dict-free tuple).
    Tokens overflowing an expert's capacity are dropped for that expert
    (Switch/GShard semantics).
    """
    T = h.shape[0]
    E, K = config.n_experts, config.experts_per_token
    logits = h.astype(jnp.float32) @ router_w            # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)      # [T, K]
    # Renormalize the top-k gates (Mixtral convention).
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    # Position of each (token, k) within its expert's capacity buffer:
    # rank tokens per expert by arrival order via cumsum over one-hots.
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)  # [T,K,E]
    flat = onehot.reshape(T * K, E)
    # K choices of one token occupy distinct slots: cumsum over the
    # flattened (token-major) order.
    pos_in_expert = (jnp.cumsum(flat, axis=0) - flat)     # [T*K, E]
    pos = (pos_in_expert * flat).sum(-1).reshape(T, K).astype(jnp.int32)
    keep = pos < capacity
    gate_vals = gate_vals * keep

    cap_onehot = jax.nn.one_hot(pos, capacity,
                                dtype=jnp.float32)        # [T, K, C]
    # [T, K, E, C] -> sum over K -> [T, E, C]
    dispatch = jnp.einsum('tke,tkc->tec', onehot,
                          cap_onehot * keep[..., None])
    combine = jnp.einsum('tke,tkc->tec', onehot,
                         cap_onehot * gate_vals[..., None])

    # Aux: Switch load-balance loss + router z-loss.
    frac_tokens = onehot.sum(1).mean(0)                  # [E]
    frac_probs = probs.mean(0)                           # [E]
    lb_loss = E * jnp.sum(frac_tokens * frac_probs)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return dispatch, combine, (lb_loss, z_loss)


def _moe_mlp(config: MoEConfig, h: jnp.ndarray, layer: Params
             ) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Routed expert MLP. h: [b, s, d]."""
    b, s, d = h.shape
    T = b * s
    E, K = config.n_experts, config.experts_per_token
    capacity = max(1, int(config.capacity_factor * T * K / E))
    flat = h.reshape(T, d)
    dispatch, combine, aux = _route(config, flat, layer['router'],
                                    capacity)
    dtype = flat.dtype
    # All-to-all happens HERE under an ep-sharded mesh: dispatch is
    # token-sharded, expert buffers are ep-sharded — XLA inserts it.
    xs = jnp.einsum('tec,td->ecd', dispatch.astype(dtype), flat)
    gate = jax.nn.silu(jnp.einsum('ecd,edf->ecf', xs, layer['w_gate']))
    up = jnp.einsum('ecd,edf->ecf', xs, layer['w_up'])
    out = jnp.einsum('ecf,efd->ecd', gate * up, layer['w_down'])
    y = jnp.einsum('tec,ecd->td', combine.astype(dtype), out)
    return y.reshape(b, s, d), aux


def _layer(config: MoEConfig, x: jnp.ndarray, layer: Params,
           cos: jnp.ndarray, sin: jnp.ndarray
           ) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    x, _, _ = llama.attention_block(config.as_llama(), x, layer, cos,
                                    sin, None)
    h = norms.rms_norm(x, layer['mlp_norm'], config.norm_eps)
    y, aux = _moe_mlp(config, h, layer)
    return x + y, aux


def forward(config: MoEConfig, params: Params, tokens: jnp.ndarray
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """tokens [b, s] -> (logits [b, s, vocab] fp32, aux losses)."""
    x = params['embed'][tokens]
    cos, sin = rope_lib.rope_frequencies(config.head_dim,
                                         config.max_seq_len,
                                         config.rope_theta)

    def body(carry, layer):
        fn = _layer
        if config.remat:
            fn = jax.checkpoint(_layer, static_argnums=(0,))
        x, aux = fn(config, carry, layer, cos, sin)
        return x, aux

    x, (lb, z) = jax.lax.scan(body, x, params['layers'])
    x = norms.rms_norm(x, params['final_norm'], config.norm_eps)
    logits = (x @ params['lm_head']).astype(jnp.float32)
    return logits, {'load_balance_loss': jnp.mean(lb),
                    'router_z_loss': jnp.mean(z)}


def loss_fn(config: MoEConfig, params: Params, tokens: jnp.ndarray,
            targets: jnp.ndarray, *, lb_coef: float = 0.01,
            z_coef: float = 1e-3) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    logits, aux = forward(config, params, tokens)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    ce = jnp.mean(nll)
    total = (ce + lb_coef * aux['load_balance_loss']
             + z_coef * aux['router_z_loss'])
    return total, {'ce_loss': ce, **aux}


def param_specs(pp_axis: Optional[str] = None):
    """PartitionSpecs for MoE params: experts over ``ep``, megatron tp on
    expert hidden dim, fsdp on model dims (compose with parallel/sharding
    conventions)."""
    from jax.sharding import PartitionSpec as P
    lead = (pp_axis,) if pp_axis else (None,)
    return {
        'embed': P('tp', 'fsdp'),
        'layers': {
            'attn_norm': P(*lead, None),
            'wq': P(*lead, 'fsdp', 'tp'),
            'wk': P(*lead, 'fsdp', 'tp'),
            'wv': P(*lead, 'fsdp', 'tp'),
            'wo': P(*lead, 'tp', 'fsdp'),
            'mlp_norm': P(*lead, None),
            'router': P(*lead, 'fsdp', None),
            'w_gate': P(*lead, 'ep', 'fsdp', 'tp'),
            'w_up': P(*lead, 'ep', 'fsdp', 'tp'),
            'w_down': P(*lead, 'ep', 'tp', 'fsdp'),
        },
        'final_norm': P(None),
        'lm_head': P('fsdp', 'tp'),
    }
