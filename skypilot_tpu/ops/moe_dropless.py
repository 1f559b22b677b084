"""A dropless expert layer that knows which experts it holds.

The router scores ALL of the model's experts and picks ``k`` of them
for each token; this chip holds ``held`` of them (``[offset, offset +
held)``, expert parallelism's share) and computes its own experts'
part of the result. No capacity: every assignment to a held expert is
computed, whatever the load. An assignment to an expert held elsewhere
is left out here, as that chip would add it after the exchange; there
is no code that stands in for the absent chips.

How: the ``T * k`` assignments are sorted by held expert (the others
last), their token rows gathered, and two grouped matrix products run
over the sorted rows with the experts' row counts as group sizes; the
rows are weighted, put back in token order and summed over ``k``. The
work grows with the assignments, not with ``T * experts``
(``models/moe.py``'s ``[T, E, C]`` dispatch grows with ``T**2`` and
drops tokens past its capacity: a served answer may not depend on a
capacity).

The grouped product is jax's Pallas kernel (``megablox.gmm``) on a TPU
and ``jax.lax.ragged_dot`` elsewhere. Both weight stacks are stored
``[held, f, d]``: the minor axis is the model width, which is
lane-aligned where an expert width such as 1856 is not.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# What a call counts (int32 scalars, in this order): assignments to
# held experts, held experts with at least one row, and the rows of the
# fullest one.
STATS = ('local_assignments', 'experts_touched', 'expert_load_max')
_TM = 128            # row tile of the grouped product on the TPU


def route(h: jnp.ndarray, router: jnp.ndarray, bias: jnp.ndarray, k: int,
          scale: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sigmoid router with a correction bias, in float32: scores ``s =
    sigmoid(h @ W_r)``, the ``k`` experts with the largest ``s + bias``,
    weights ``s[chosen] / (sum(s[chosen]) + 1e-20) * scale``. The
    weights are normalised over all ``k``, held here or not. Returns
    (idx ``[T, k]`` int32, w ``[T, k]`` float32)."""
    s = jax.nn.sigmoid(jnp.dot(h.astype(jnp.float32),
                               router.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32)[None, :], k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    w = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20) * scale
    return idx.astype(jnp.int32), w


def grouped_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray,
                   group_sizes: jnp.ndarray, *, transpose_rhs: bool,
                   impl: str = 'auto',
                   interpret: bool = False) -> jnp.ndarray:
    """``lhs[rows of group g] @ rhs[g]`` (``rhs[g].T`` with
    ``transpose_rhs``) for consecutive row groups. lhs ``[M, K]``, rhs
    ``[G, K, N]`` or ``[G, N, K]``, group_sizes ``[G]`` int32. Rows
    past the last group come back unspecified: the caller masks them.
    ``M`` must be a multiple of 128 for ``impl='pallas'``."""
    if impl == 'auto':
        impl = 'pallas' if jax.default_backend() == 'tpu' else 'ragged_dot'
    if impl == 'ragged_dot':
        if transpose_rhs:
            rhs = jnp.swapaxes(rhs, 1, 2)
        return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                                  preferred_element_type=lhs.dtype)
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    M, K = lhs.shape
    N = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm = min(_TM, M)
    # The contraction is tiled in lane multiples and the output columns
    # in thirds where that is lane-aligned; an axis that is not (an
    # expert width of 1856) is taken whole.
    tk = next((t for t in (512, 384, 256, 128) if K % t == 0), K)
    tn = N // 3 if N % 384 == 0 else N
    return gmm(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
               tiling=(tm, tk, tn), transpose_rhs=transpose_rhs,
               interpret=interpret)


def local_experts(h: jnp.ndarray, idx: jnp.ndarray, w: jnp.ndarray,
                  w_up: jnp.ndarray, w_down: jnp.ndarray,
                  valid: Optional[jnp.ndarray] = None, offset: int = 0,
                  *, w_gate: Optional[jnp.ndarray] = None,
                  impl: str = 'auto', interpret: bool = False
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The held experts' part of ``sum_chosen w_e * relu(h @ U_e)**2 @
    D_e`` or, given ``w_gate`` (the gated form), of ``sum_chosen w_e *
    (silu(h @ G_e) * (h @ U_e)) @ D_e``. h ``[T, d]``; idx / w ``[T,
    k]`` from ``route``; w_up, w_down and w_gate ``[held, f, d]``
    (experts ``offset .. offset + held``); valid ``[T]`` bool: rows
    that are padding touch no expert. Returns (``[T, d]`` float32, the
    ``STATS`` counts ``[3]`` int32)."""
    T, d = h.shape
    k, held = idx.shape[1], w_up.shape[0]
    local = (idx >= offset) & (idx < offset + held)
    if valid is not None:
        local = local & valid[:, None]
    # Sort the T*k assignments by held expert; the rest sort last and
    # fall outside every group.
    key = jnp.where(local, idx - offset, held).reshape(T * k)
    M = -(-T * k // _TM) * _TM if T * k > _TM else T * k
    key = jnp.pad(key, (0, M - T * k), constant_values=held)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                    dtype=jnp.int32)
    n_local = jnp.sum(sizes)
    rows = jnp.arange(M) < n_local
    token = jnp.minimum(order // k, T - 1)
    xs = jnp.where(rows[:, None], h[token], 0)
    up = grouped_matmul(xs, w_up, sizes, transpose_rhs=True, impl=impl,
                        interpret=interpret)
    up = jnp.where(rows[:, None], up, 0).astype(jnp.float32)
    if w_gate is None:
        act = jnp.square(jax.nn.relu(up)).astype(h.dtype)
    else:
        gate = grouped_matmul(xs, w_gate, sizes, transpose_rhs=True,
                              impl=impl, interpret=interpret)
        gate = jnp.where(rows[:, None], gate, 0).astype(jnp.float32)
        act = (jax.nn.silu(gate) * up).astype(h.dtype)
    down = grouped_matmul(act, w_down, sizes, transpose_rhs=False,
                          impl=impl, interpret=interpret)
    down = jnp.where(rows[:, None], down, 0).astype(jnp.float32)
    # Back to assignment order (each sorted row to its own place), then
    # the weighted sum over a token's k assignments.
    back = jnp.zeros((M,), jnp.int32).at[order].set(
        jnp.arange(M, dtype=jnp.int32))
    per = down[back[:T * k]].reshape(T, k, d)
    out = jnp.sum(per * w[:, :, None], axis=1)
    stats = jnp.stack([n_local, jnp.sum(sizes > 0, dtype=jnp.int32),
                       jnp.max(sizes)])
    return out, stats
