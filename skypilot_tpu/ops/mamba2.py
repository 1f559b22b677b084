"""Mamba-2 (SSD) state-space mixer: the recurrence two ways.

Per head ``h`` (group ``g = h // (H / G)``) and step ``t``::

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t[:, None] * B_t[g][None, :]
    y_t = S_t @ C_t[g] + D * x_t

with ``S`` ``[P, N]`` float32. ``ssd_decode_step`` is that line for one
token of every slot. ``ssd_chunk_scan`` computes the same thing for a
prompt chunk of one sequence in blocks of ``chunk`` steps (the SSD
form: inside a block the outputs are a masked matrix product, between
blocks only the state is carried), starting from a carried state. Both
are float32 throughout with full-precision products: the state lives
for thousands of steps, and its error is the whole model's.

A step with ``dt = 0`` leaves the state exactly as it was
(``exp(0) * S + 0``): that is how a chunk's padded tail is kept out of
the state.

Plain ``jax.numpy``: a block's work is a few small batched products
beside the layer's projections; no kernel is needed until a trace says
so (scope ``ssm`` in the step programs).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def causal_conv(window: jnp.ndarray, w: jnp.ndarray,
                b: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal convolution over time. window ``[k-1+T, c]``:
    the ``k-1`` inputs before the chunk, then the chunk; w ``[k, c]``
    (``w[k-1]`` weighs the current step); b ``[c]``. Returns ``[T, c]``
    float32."""
    k = w.shape[0]
    T = window.shape[0] - (k - 1)
    win = window.astype(jnp.float32)
    out = b.astype(jnp.float32)[None, :]
    for j in range(k):
        out = out + win[j:j + T] * w[j].astype(jnp.float32)[None, :]
    return out


def _heads(v: jnp.ndarray, n_heads: int) -> jnp.ndarray:
    """``[.., G, N]`` -> ``[.., H, N]``: each group serves H/G heads."""
    return jnp.repeat(v, n_heads // v.shape[-2], axis=-2)


def ssd_decode_step(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
                    b: jnp.ndarray, c: jnp.ndarray, d_skip: jnp.ndarray,
                    state: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One step of every slot. x ``[S, H, P]``, dt ``[S, H]``, a ``[H]``
    (negative), b / c ``[S, G, N]``, d_skip ``[H]``, state ``[S, H, P,
    N]``; all float32. Returns (y ``[S, H, P]``, state')."""
    H = x.shape[1]
    bh, ch = _heads(b, H), _heads(c, H)
    decay = jnp.exp(dt * a[None, :])
    state = (decay[:, :, None, None] * state
             + (dt[:, :, None] * x)[..., None] * bh[:, :, None, :])
    y = jnp.sum(state * ch[:, :, None, :], axis=-1)
    return y + d_skip[None, :, None] * x, state


def ssd_chunk_scan(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
                   b: jnp.ndarray, c: jnp.ndarray, d_skip: jnp.ndarray,
                   state: jnp.ndarray, *, chunk: int = 128
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``T`` steps of one sequence from a carried state. x ``[T, H,
    P]``, dt ``[T, H]`` (0 where a step must not count), a ``[H]``, b /
    c ``[T, G, N]``, state ``[H, P, N]``; float32. ``T`` must be a
    multiple of the block (``min(chunk, T)``). Returns (y ``[T, H,
    P]``, the state after step ``T - 1``)."""
    T, H, P = x.shape
    L = min(chunk, T)
    if T % L:
        raise ValueError(f'{T} steps do not divide into blocks of {L}')
    G = b.shape[1]
    per = H // G
    lower = jnp.tril(jnp.ones((L, L), bool))

    def block(s_prev, xs):
        xb, dtb, bb, cb = xs                       # [L, ...]
        cum = jnp.cumsum(dtb * a[None, :], axis=0)          # [L, H] <= 0
        # Inside the block: step j reaches step i >= j through
        # exp(cum_i - cum_j) * dt_j, weighted by C_i . B_j of the group.
        cb_g = jnp.einsum('ign,jgn->gij', cb, bb, precision=_HI)
        seg = cum.T[:, :, None] - cum.T[:, None, :]         # [H, i, j]
        reach = jnp.where(lower[None], jnp.exp(
            jnp.where(lower[None], seg, 0.0)), 0.0)
        m = (jnp.repeat(cb_g, per, axis=0) * reach
             * dtb.T[:, None, :])                           # [H, i, j]
        y = jnp.einsum('hij,jhp->ihp', m, xb, precision=_HI)
        # From the carried state: decayed to step i, read through C_i.
        ch = _heads(cb, H)                                  # [L, H, N]
        y = y + jnp.exp(cum)[:, :, None] * jnp.einsum(
            'hpn,ihn->ihp', s_prev, ch, precision=_HI)
        # The state at the block's end.
        tail = jnp.exp(cum[-1][None, :] - cum) * dtb        # [L, H]
        s_new = (jnp.exp(cum[-1])[:, None, None] * s_prev
                 + jnp.einsum('jhp,jhn->hpn', tail[:, :, None] * xb,
                              _heads(bb, H), precision=_HI))
        return s_new, y

    nb = T // L
    xs = (x.reshape(nb, L, H, P), dt.reshape(nb, L, H),
          b.reshape(nb, L, G, -1), c.reshape(nb, L, G, -1))
    state, y = jax.lax.scan(block, state, xs)
    return y.reshape(T, H, P) + d_skip[None, :, None] * x, state
