"""Rotary position embeddings (RoPE), Llama-3 style."""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp


def _inv_freq(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def rope_frequencies(head_dim: int, max_seq_len: int,
                     theta: float = 500_000.0) -> Tuple[jnp.ndarray,
                                                        jnp.ndarray]:
    """Precomputed (cos, sin) tables, shape [max_seq_len, head_dim//2],
    fp32 (precision matters at long context)."""
    inv_freq = _inv_freq(head_dim, theta)
    t = jnp.arange(max_seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    return jnp.cos(freqs), jnp.sin(freqs)


def rope_at(head_dim: int, theta: float,
            positions: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(cos, sin) of ``rope_frequencies`` at ``positions [seq]`` alone,
    ``[seq, head_dim//2]`` fp32, with no table behind them: what
    ``apply_rope`` takes with ``positions=None``. For a program whose
    model declares far more positions than a step touches."""
    inv_freq = _inv_freq(head_dim, theta)
    freqs = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray,
               positions: jnp.ndarray = None) -> jnp.ndarray:
    """Rotate pairs of channels. x: [..., seq, heads, head_dim].

    `positions`: optional [..., seq] absolute positions (used by
    sequence-parallel shards and decode caches); defaults to arange.
    """
    seq = x.shape[-3]
    if positions is None:
        c = cos[:seq][..., None, :]   # [seq, 1, hd/2]
        s = sin[:seq][..., None, :]
    else:
        c = cos[positions][..., None, :]
        s = sin[positions][..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)
