"""Ring attention: exact attention over sequence-sharded inputs.

Long-context is first-class (SURVEY.md §2.8: the reference has *no*
sequence parallelism — greenfield here). Each device holds a sequence shard
of Q/K/V; K/V blocks rotate around the mesh axis ring via ``ppermute``
(ICI-neighbor exchange) while a blockwise online softmax accumulates exact
results — attention memory stays O(seq/N) per device and compute overlaps
with the rotation.

Usage: inside ``shard_map`` with q/k/v sharded on the sequence axis::

    out = shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name='sp'),
        mesh=mesh,
        in_specs=P(None, None, 'sp', None), out_specs=P(None, None, 'sp',
        None))(q, k, v)

(Blockwise formulation after Liu et al., "Ring Attention with Blockwise
Transformers" — public technique; implementation is original.)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   axis_name: str, *, causal: bool = True,
                   sm_scale: Optional[float] = None) -> jnp.ndarray:
    """q/k/v: local shards [b, h, s_local, d] on a ring of `axis_name`.

    GQA: pass k/v with fewer heads; they are expanded locally (head count
    is small relative to seq shards, so this is cheap).
    """
    b, hq, s_local, d = q.shape
    hkv = k.shape[1]
    if hkv != hq:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if sm_scale is None:
        sm_scale = d ** -0.5

    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    q32 = q.astype(jnp.float32) * sm_scale
    q_pos = my_idx * s_local + jnp.arange(s_local)

    def step(i, carry):
        k_blk, v_blk, acc, m, l = carry
        # The block we hold at ring step i originated at device (idx - i).
        src = (my_idx - i) % n
        k_pos = src * s_local + jnp.arange(s_local)
        s = jnp.einsum('bhqd,bhkd->bhqk', q32, k_blk.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # Fully-masked rows keep m = -inf; guard the exp.
        m_safe = jnp.where(jnp.isfinite(m_new) | (m_new > _NEG_INF / 2),
                           m_new, 0.0)
        p = jnp.exp(s - m_safe)
        p = jnp.where(s <= _NEG_INF / 2, 0.0, p)
        alpha = jnp.where(m > _NEG_INF / 2, jnp.exp(m - m_safe), 0.0)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            'bhqk,bhkd->bhqd', p, v_blk.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        # Rotate K/V to the next device (ICI neighbor exchange). XLA
        # overlaps this ppermute with the next step's compute.
        k_next = jax.lax.ppermute(k_blk, axis_name, perm)
        v_next = jax.lax.ppermute(v_blk, axis_name, perm)
        return k_next, v_next, acc_new, m_new, l_new

    # Accumulator inits must be tagged as device-varying over the ring axis
    # (the loop writes axis-dependent values into them).
    def varying(x):
        return jax.lax.pcast(x, (axis_name,), to='varying')

    init = (
        k, v,
        varying(jnp.zeros((b, hq, s_local, d), jnp.float32)),
        varying(jnp.full((b, hq, s_local, 1), _NEG_INF, jnp.float32)),
        varying(jnp.zeros((b, hq, s_local, 1), jnp.float32)),
    )
    _, _, acc, _, l = jax.lax.fori_loop(0, n, step, init)
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
