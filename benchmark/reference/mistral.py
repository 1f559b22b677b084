"""Plain reference for the Mistral dense block, in float32.

Written from the published description of the architecture
(``MistralForCausalLM``: pre-norm residual blocks, RMSNorm, grouped-query
attention with rotary embeddings in the rotate-half convention, SwiGLU
MLP, untied output head) and importing nothing from the program. No
kernels, no cache, no batching: one sequence at a time through one
layer at a time, every matrix product at ``highest`` precision (on a
TPU a float32 product otherwise runs in bfloat16 passes).

``act`` selects the precision of the *activations*: ``None`` is the
reference itself, float32 throughout. ``'w8a8'`` is the control of
``benchmark/check.py``, the step below the configuration's bfloat16
activations that would tempt a later PR: every weight product (the
seven of a block and the head) takes its left input in int8, one absmax
scale per token row, and all else stays float32. ``'int8'``, ``'fp8'``
(float8_e4m3fn) and ``'bf16'`` round every tensor that a bfloat16
program rounds to that precision instead; ``'bf16'`` is the precision
the configuration states, and ``'bf16-w8a8'`` is ``'w8a8'`` on top of
it. Departure from the published model: none in the mathematics; the
weights are the seeded int8 pairs of ``benchmark/weights.py``,
dequantised to float32 by the caller.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def precisions(act: Optional[str]):
    """``act`` as (every rounded tensor, the weight products' inputs)."""
    return {None: (None, None), 'w8a8': (None, 'int8'),
            'bf16-w8a8': ('bf16', 'int8')}.get(act, (act, act))


def lower_precision(x: jnp.ndarray, act: Optional[str]) -> jnp.ndarray:
    if act is None:
        return x
    if act == 'int8':
        scale = jnp.maximum(
            jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0, 1e-12)
        return jnp.clip(jnp.round(x / scale), -127, 127) * scale
    if act == 'fp8':
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    if act == 'bf16':
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    raise ValueError(f'unknown activation precision {act!r}')


def matmul(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    return jnp.matmul(x, w, precision=HIGHEST)


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def rotate_half(x: jnp.ndarray) -> jnp.ndarray:
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [seq, heads, head_dim]; positions: [seq]."""
    hd = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    freqs = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    return x * jnp.cos(emb) + rotate_half(x) * jnp.sin(emb)


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """Causal grouped-query attention. q: [s, hq, hd]; k, v: [s, hkv, hd]."""
    s, hq, hd = q.shape
    group = hq // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum('qhd,khd->hqk', q, k, precision=HIGHEST) * hd ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum('hqk,khd->qhd', probs, v, precision=HIGHEST)


def layer_forward(cfg: Dict[str, Any], w: Dict[str, jnp.ndarray],
                  x: jnp.ndarray, act: Optional[str] = None) -> jnp.ndarray:
    """One block on one sequence. x: [seq, hidden] float32; ``w`` holds
    float32 matrices ``[in, out]`` and the two norm vectors. ``act``:
    see ``precisions``; the tensors a bfloat16 program would round are
    each product's inputs and result, each norm, rotation, attention
    output and residual sum."""
    every, fed = precisions(act)

    def r(t):
        return lower_precision(t, every)

    def mm(t, weight):
        return matmul(lower_precision(t, fed), weight)
    s = x.shape[0]
    hq, hkv, hd = (cfg['num_attention_heads'], cfg['num_key_value_heads'],
                   cfg['head_dim'])
    eps, theta = cfg['rms_norm_eps'], cfg['rope_theta']
    positions = jnp.arange(s)
    h = r(rms_norm(x, w['attn_norm'], eps))
    q = r(rope(r(mm(h, w['wq'])).reshape(s, hq, hd), positions, theta))
    k = r(rope(r(mm(h, w['wk'])).reshape(s, hkv, hd), positions, theta))
    v = r(mm(h, w['wv'])).reshape(s, hkv, hd)
    att = r(attention(q, k, v).reshape(s, hq * hd))
    x = r(x + r(mm(att, w['wo'])))
    h = r(rms_norm(x, w['mlp_norm'], eps))
    gate = r(jax.nn.silu(r(mm(h, w['w_gate']))))
    inner = r(gate * r(mm(h, w['w_up'])))
    return r(x + r(mm(inner, w['w_down'])))


def embed(table: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    return table[tokens]


def head(cfg: Dict[str, Any], final_norm: jnp.ndarray, lm_head: jnp.ndarray,
         x: jnp.ndarray, act: Optional[str] = None) -> jnp.ndarray:
    """Logits of the rows given (float32, as the program's are).
    x: [rows, hidden]."""
    every, fed = precisions(act)
    h = lower_precision(rms_norm(x, final_norm, cfg['rms_norm_eps']), every)
    return matmul(lower_precision(h, fed), lm_head)


def forward(cfg: Dict[str, Any], weights: Dict[str, Any],
            tokens: jnp.ndarray, act: Optional[str] = None) -> jnp.ndarray:
    """Whole forward pass of one sequence, for tests at small sizes.
    ``weights``: ``{'embed', 'layers': [per-layer dict], 'final_norm',
    'lm_head'}``, all float32. Returns logits [seq, vocab]."""
    x = lower_precision(embed(weights['embed'], tokens), precisions(act)[0])
    for w in weights['layers']:
        x = layer_forward(cfg, w, x, act)
    return head(cfg, weights['final_norm'], weights['lm_head'], x, act)
