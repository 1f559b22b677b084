"""Vocab-chunked cross-entropy with a custom VJP.

The naive path materializes fp32 logits [tokens, vocab] (1.6 GB on the
1B bench), then log-softmax walks that tensor several more times, and
autodiff stores/rebuilds it for the backward — all HBM traffic, no
MXU work. This version streams the vocabulary in chunks with an online
logsumexp (the flash-attention trick applied to the loss):

- forward: one [T, C] fp32 buffer per chunk; accumulates (max, sumexp,
  target-logit) — never more than T*C live.
- backward: recomputes each chunk's logits (one extra logits matmul —
  MXU flops are cheap; the avoided HBM round trips are not), forms
  P - onehot per chunk, and feeds the SAME dX / dW matmuls autodiff
  would run.

Numerics match the dense fp32 log-softmax to float32 tolerance (tested
against the dense oracle in test_ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def chunked_cross_entropy(x: jnp.ndarray, w: jnp.ndarray,
                          targets: jnp.ndarray,
                          num_chunks: int = 8) -> jnp.ndarray:
    """Per-token NLL of ``softmax(x @ w)`` at ``targets``.

    x: [T, d] (compute dtype); w: [d, V]; targets: [T] int32.
    Returns [T] fp32. V must divide by num_chunks.
    """
    nll, _ = _ce_fwd_impl(x, w, targets, num_chunks)
    return nll


def _chunk(w: jnp.ndarray, i: jnp.ndarray, c: int) -> jnp.ndarray:
    return jax.lax.dynamic_slice(w, (0, i * c), (w.shape[0], c))


def _ce_fwd_impl(x, w, targets, num_chunks):
    t = x.shape[0]
    v = w.shape[1]
    assert v % num_chunks == 0, (v, num_chunks)
    c = v // num_chunks

    def body(carry, i):
        m, l, tl = carry
        logits = (x @ _chunk(w, i, c)).astype(jnp.float32)   # [T, C]
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        l = l * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[:, None]), axis=-1)
        in_chunk = (targets >= i * c) & (targets < (i + 1) * c)
        idx = jnp.clip(targets - i * c, 0, c - 1)
        picked = jnp.take_along_axis(logits, idx[:, None], axis=1)[:, 0]
        tl = tl + jnp.where(in_chunk, picked, 0.0)
        return (m_new, l, tl), None

    init = (jnp.full((t,), -jnp.inf, jnp.float32),
            jnp.zeros((t,), jnp.float32),
            jnp.zeros((t,), jnp.float32))
    (m, l, tl), _ = jax.lax.scan(body, init,
                                 jnp.arange(num_chunks, dtype=jnp.int32))
    lse = m + jnp.log(l)
    return lse - tl, lse


def _ce_fwd(x, w, targets, num_chunks):
    # (nondiff_argnums args reach the fwd rule at their ORIGINAL
    # positions; only the bwd rule gets them as leading args.)
    nll, lse = _ce_fwd_impl(x, w, targets, num_chunks)
    return nll, (x, w, targets, lse)


def _ce_bwd(num_chunks, res, g):
    x, w, targets, lse = res
    d = x.shape[1]
    v = w.shape[1]
    c = v // num_chunks
    gx32 = g.astype(jnp.float32)

    def body(dx, i):
        wc = _chunk(w, i, c)
        logits = (x @ wc).astype(jnp.float32)                # [T, C]
        p = jnp.exp(logits - lse[:, None])                   # softmax
        in_chunk = (targets >= i * c) & (targets < (i + 1) * c)
        idx = jnp.clip(targets - i * c, 0, c - 1)
        onehot = (jax.nn.one_hot(idx, c, dtype=jnp.float32) *
                  in_chunk[:, None].astype(jnp.float32))
        dlogits = ((p - onehot) * gx32[:, None]).astype(x.dtype)
        dx = dx + dlogits @ wc.T                             # [T, d]
        dwc = x.T @ dlogits                                  # [d, C]
        return dx, dwc

    dx0 = jnp.zeros(x.shape, x.dtype)
    dx, dw_chunks = jax.lax.scan(
        body, dx0, jnp.arange(num_chunks, dtype=jnp.int32))
    # [nc, d, C] -> [d, V]
    dw = jnp.transpose(dw_chunks, (1, 0, 2)).reshape(d, v)
    return dx.astype(x.dtype), dw.astype(w.dtype), None


chunked_cross_entropy.defvjp(_ce_fwd, _ce_bwd)


# ---------------------------------------------------------------------------
# Fused Pallas cross-entropy: logits never leave VMEM.
# ---------------------------------------------------------------------------
# The chunked path above kills the [T, V] materialization but still
# dispatches one XLA matmul per vocab chunk and round-trips each chunk's
# fp32 logits through HBM. The fused FORWARD moves the loss into Pallas:
# each grid step computes one [bt, bv] logits tile ON THE MXU, consumes
# it (online logsumexp + target pick) while it is still in VMEM, and
# throws it away — HBM traffic is just x + W, instead of the dense
# path's 4+ passes over [T, V] fp32 (measured ~25 ms of the 1B bench
# forward at 32k vocab). The BACKWARD stays in XLA with exactly one
# logits recompute — see _fused_bwd_rule's docstring for why the
# fully-Pallas two-kernel backward measured slower.
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Vocab size above which the fused backward switches from the one-shot
# fp32 recompute to the chunked scan (fp32 [T, V] logits alone exceed
# 6 GB at Llama-3's 128k vocab). Module-level so tests can lower it.
ONE_SHOT_BWD_MAX_VOCAB = 65536
# Largest x ([block_t, d]) or w ([d, block_v]) tile the fused forward
# picks by default.
_TILE_BYTES = 2 << 20


def _ce_fwd_kernel(x_ref, w_ref, t_ref, nll_ref, lse_ref,
                   m_ref, l_ref, tl_ref, *, bv: int, n_v: int):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        tl_ref[...] = jnp.zeros_like(tl_ref)

    x = x_ref[...]
    w = w_ref[...]
    logits = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # [bt, bv]
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1,
                                        keepdims=True))
    l_ref[...] = (l_ref[...] * jnp.exp(m_prev - m_new)
                  + jnp.sum(jnp.exp(logits - m_new), axis=-1,
                            keepdims=True))
    m_ref[...] = m_new
    cols = vi * bv + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    is_t = cols == t_ref[...]                        # [bt, 1] broadcast
    tl_ref[...] = tl_ref[...] + jnp.sum(
        jnp.where(is_t, logits, 0.0), axis=-1, keepdims=True)

    @pl.when(vi == n_v - 1)
    def _finalize():
        lse = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))
        nll_ref[...] = lse - tl_ref[...]
        lse_ref[...] = lse


def _fused_dims(t, v, block_t, block_v):
    assert t % block_t == 0, (t, block_t)
    assert v % block_v == 0, (v, block_v)
    return t // block_t, v // block_v


def _fused_fwd(x, w, targets, block_t, block_v, interpret):
    t, d = x.shape
    v = w.shape[1]
    n_t, n_v = _fused_dims(t, v, block_t, block_v)
    t2 = targets.astype(jnp.int32).reshape(t, 1)
    kernel = functools.partial(_ce_fwd_kernel, bv=block_v, n_v=n_v)
    nll, lse = pl.pallas_call(
        kernel,
        grid=(n_t, n_v),
        in_specs=[
            pl.BlockSpec((block_t, d), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((d, block_v), lambda ti, vi: (0, vi)),
            pl.BlockSpec((block_t, 1), lambda ti, vi: (ti, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, 1), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((block_t, 1), lambda ti, vi: (ti, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, 1), jnp.float32),
            jax.ShapeDtypeStruct((t, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_t, 1), jnp.float32),
            pltpu.VMEM((block_t, 1), jnp.float32),
            pltpu.VMEM((block_t, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, w, t2)
    return nll[:, 0], lse


def _auto_block(n: int, want: int, floor: int = 8) -> int:
    """Largest power-of-two-ish tile <= want that divides n (Llama-3's
    128256 vocab divides 256, not 512)."""
    b = want
    while b > floor and n % b:
        b //= 2
    if n % b:
        import math
        b = math.gcd(b, n)
    return max(b, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_cross_entropy(x, w, targets, block_t, block_v, interpret):
    nll, _ = _fused_fwd(x, w, targets, block_t, block_v, interpret)
    return nll


def fused_cross_entropy(x: jnp.ndarray, w: jnp.ndarray,
                        targets: jnp.ndarray,
                        block_t: 'Optional[int]' = None,
                        block_v: 'Optional[int]' = None,
                        interpret: 'Optional[bool]' = None
                        ) -> jnp.ndarray:
    """Per-token NLL of ``softmax(x @ w)`` at ``targets``, fused
    forward (logits tiles never leave VMEM) + single-recompute XLA
    backward.

    x: [T, d]; w: [d, V]; targets: [T] int32 -> [T] fp32. Tile sizes
    default to the largest divisors of T / V up to 512, capped so one
    x tile and one w tile stay within ``_TILE_BYTES`` each: the
    pipeline double-buffers both in VMEM, and 512 x d tiles at d=4096
    overran the 16 MiB scoped limit on v5e. `interpret` defaults to
    True off-TPU.
    """
    if interpret is None:
        interpret = jax.default_backend() != 'tpu'
    d = x.shape[1]
    rows = _TILE_BYTES // (d * max(x.dtype.itemsize, w.dtype.itemsize))
    cap = max(128, min(512, 1 << (max(rows, 1).bit_length() - 1)))
    bt = block_t or _auto_block(x.shape[0], cap)
    bv = block_v or _auto_block(w.shape[1], cap, floor=128)
    return _fused_cross_entropy(x, w, targets, bt, bv, interpret)


def _fused_fwd_rule(x, w, targets, block_t, block_v, interpret):
    if interpret is None:
        interpret = jax.default_backend() != 'tpu'
    nll, lse = _fused_fwd(x, w, targets, block_t, block_v, interpret)
    return nll, (x, w, targets, lse)


def _fused_bwd_rule(block_t, block_v, interpret, res, g):
    """Backward in plain XLA, recomputing the logits ONCE.

    A fully-Pallas backward (dx kernel + dW kernel, each recomputing
    its logits tile — the flash-attention decomposition) was built and
    MEASURED SLOWER on the 1B bench: CE's cost IS the matmul, so two
    recomputes (4 total matmul units vs autodiff's 3) overwhelm the
    HBM passes they save — d=1536's flops/byte ratio keeps that true
    at every vocab size. The winning split: Pallas forward (logits
    tiles never leave VMEM — that pass was ~60% softmax/materialization
    overhead) + one XLA recompute feeding both grad matmuls through a
    bf16 P (one materialized [T, V] round trip, half the fp32 bytes,
    and exactly the dX/dW matmuls autodiff would run).
    """
    del block_t, block_v, interpret
    x, w, targets, lse = res
    t = x.shape[0]
    v = w.shape[1]
    if v <= ONE_SHOT_BWD_MAX_VOCAB:
        # One-shot recompute: a single fp32 [T, V] round trip.
        logits = (x @ w).astype(jnp.float32)
        p = jnp.exp(logits - lse)                   # lse: [T, 1]
        p = p.at[jnp.arange(t), targets].add(-1.0)
        p = (p * g.astype(jnp.float32)[:, None]).astype(x.dtype)
        dx = p @ w.T
        dw = x.T @ p
        return dx.astype(x.dtype), dw.astype(w.dtype), None
    # Large vocab: the one-shot fp32 logits alone are 6+ GB at
    # Llama-3's 128k — reuse the chunked backward (same math, [T, C]
    # live at a time).
    c = _auto_block(v, 8192, floor=128)
    return _ce_bwd(v // c, (x, w, targets, lse[:, 0]), g)


_fused_cross_entropy.defvjp(_fused_fwd_rule, _fused_bwd_rule)
