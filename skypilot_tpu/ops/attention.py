"""Attention: dense reference + Pallas TPU flash-attention kernel.

The hot op of every model in the framework. Two implementations with one
numerically-identical contract (inputs [batch, heads, seq, head_dim], GQA
via fewer KV heads):

- ``dense_attention``: O(seq^2)-memory einsum+softmax. XLA fuses this well;
  it is the differentiable training fallback and the ground truth in tests.
- ``flash_attention``: Pallas kernels, online-softmax over KV blocks, causal
  block skipping, fp32 accumulators, O(seq) memory — forward AND backward
  (FlashAttention-2 style: forward saves the per-row logsumexp; backward
  runs a dq kernel gridded over Q blocks and a dk/dv kernel gridded over
  KV blocks, each recomputing P from the saved statistics instead of
  materializing the O(s^2) probability matrix).

Kernel design notes (per /opt/skills/guides/pallas_guide.md):
- grid (batch, q_heads, seq/block_q); K/V blocks for the mapped KV head are
  resident in VMEM; the inner fori_loop walks KV blocks with an early upper
  bound under causality (skips fully-masked blocks, ~2x for causal).
- GQA is folded into the BlockSpec index_map (head -> head // group), so no
  KV replication is materialized in HBM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from skypilot_tpu.parallel import sharding as sharding_lib

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256
_NEG_INF = -1e30


def dense_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    *, causal: bool = True,
                    sm_scale: Optional[float] = None) -> jnp.ndarray:
    """Reference attention. q: [b, hq, s, d]; k/v: [b, hkv, s, d]."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    if sm_scale is None:
        sm_scale = d ** -0.5
    if hkv != hq:
        assert hq % hkv == 0
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    logits = jnp.einsum('bhqd,bhkd->bhqk', q, k,
                        preferred_element_type=jnp.float32) * sm_scale
    if causal:
        mask = jnp.tril(jnp.ones((s, s), dtype=bool))
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum('bhqk,bhkd->bhqd', probs, v)


# ---------------------------------------------------------------------------
# Pallas flash attention (forward)
# ---------------------------------------------------------------------------
def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                      sm_scale: float, causal: bool,
                      block_q: int, block_k: int, seq_len: int):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * sm_scale    # [block_q, d]
    head_dim = q.shape[-1]

    num_k_blocks = pl.cdiv(seq_len, block_k)
    if causal:
        # Last KV block that any row of this Q block can see.
        upper = jax.lax.div((qi + 1) * block_q + block_k - 1, block_k)
        upper = jnp.minimum(upper, num_k_blocks)
    else:
        upper = num_k_blocks

    def body(j, carry):
        acc, m, l = carry
        k_blk = k_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(
            jnp.float32)                                   # [block_k, d]
        v_blk = v_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(
            jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bq, bk]
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    init = (
        jnp.zeros((block_q, head_dim), jnp.float32),
        jnp.full((block_q, 1), _NEG_INF, jnp.float32),
        jnp.zeros((block_q, 1), jnp.float32),
    )
    acc, m, l = jax.lax.fori_loop(0, upper, body, init)
    l = jnp.maximum(l, 1e-30)
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
    # Per-row softmax statistic for the backward pass: lse = m + log(l)
    # lets both bwd kernels rebuild P = exp(S - lse) blockwise. Stored as
    # [b, hq, 1, s]: TPU blocks need their last two dims (8,128)-divisible
    # or equal to the array dims, which (1, block_q) satisfies.
    lse_ref[0, 0, 0] = (m + jnp.log(l))[:, 0]


def _flash_forward(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   causal: bool, sm_scale: float,
                   block_q: int, block_k: int,
                   interpret: bool):
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (
        f'seq_len {s} must be a multiple of block sizes '
        f'({block_q}, {block_k})')
    grid = (b, hq, s // block_q)
    kernel = functools.partial(
        _flash_fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, seq_len=s)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, s, d),
                         lambda bi, hi, qi, g=group: (bi, hi // g, 0, 0)),
            pl.BlockSpec((1, 1, s, d),
                         lambda bi, hi, qi, g=group: (bi, hi // g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda bi, hi, qi: (bi, hi, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, hq, 1, s), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# Pallas flash attention (backward) — FlashAttention-2 decomposition:
#   delta_i = rowsum(dO_i * O_i)                  (precomputed, fused by XLA)
#   P_ij    = exp(S_ij - lse_i)
#   dV_j    = sum_i P_ij^T @ dO_i
#   dS_ij   = P_ij * (dO_i @ V_j^T - delta_i)
#   dQ_i    = sum_j dS_ij @ K_j * sm_scale
#   dK_j    = sum_i dS_ij^T @ Q_i * sm_scale
# dQ is gridded over Q blocks (rows), dK/dV over KV blocks (columns), so
# every accumulator lives in registers/VMEM and nothing O(s^2) hits HBM.
# ---------------------------------------------------------------------------
def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, sm_scale: float, causal: bool,
                         block_q: int, block_k: int, seq_len: int):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)               # [bq, d]
    do = do_ref[0, 0].astype(jnp.float32)             # [bq, d]
    lse = lse_ref[0, 0, 0][:, None]                   # [bq, 1]
    delta = delta_ref[0, 0, 0][:, None]               # [bq, 1]

    num_k_blocks = pl.cdiv(seq_len, block_k)
    if causal:
        upper = jax.lax.div((qi + 1) * block_q + block_k - 1, block_k)
        upper = jnp.minimum(upper, num_k_blocks)
    else:
        upper = num_k_blocks

    def body(j, dq):
        k_blk = k_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(
            jnp.float32)                               # [bk, d]
        v_blk = v_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(
            jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # [bq, bk]
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse)                           # [bq, bk]
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bq, bk]
        ds = p * (dp - delta)
        return dq + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(
        0, upper, body, jnp.zeros((block_q, q.shape[-1]), jnp.float32))
    dq_ref[0, 0] = (dq * sm_scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, sm_scale: float, causal: bool,
                          block_q: int, block_k: int, seq_len: int):
    kj = pl.program_id(2)
    k_blk = k_ref[0, 0].astype(jnp.float32)           # [bk, d]
    v_blk = v_ref[0, 0].astype(jnp.float32)           # [bk, d]
    head_dim = k_blk.shape[-1]

    num_q_blocks = pl.cdiv(seq_len, block_q)
    # First Q block whose rows can see any column of this KV block.
    lower = jax.lax.div(kj * block_k, block_q) if causal else 0

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, 0, pl.ds(i * block_q, block_q), :].astype(
            jnp.float32)                               # [bq, d]
        do = do_ref[0, 0, pl.ds(i * block_q, block_q), :].astype(
            jnp.float32)
        lse = lse_ref[0, 0, 0, pl.ds(i * block_q, block_q)][:, None]
        delta = delta_ref[0, 0, 0, pl.ds(i * block_q, block_q)][:, None]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # [bq, bk]
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse)                           # [bq, bk]
        dv_new = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bk, d]
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bq, bk]
        ds = p * (dp - delta)
        dk_new = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bk, d]
        return dk_new, dv_new

    dk, dv = jax.lax.fori_loop(
        lower, num_q_blocks, body,
        (jnp.zeros((block_k, head_dim), jnp.float32),
         jnp.zeros((block_k, head_dim), jnp.float32)))
    dk_ref[0, 0] = (dk * sm_scale).astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, causal, sm_scale,
                    block_q, block_k, interpret):
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    # delta = rowsum(dO * O): one fused elementwise+reduce, O(s) memory.
    # Shaped [b, hq, 1, s] to match lse's TPU-friendly block layout.
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, :, None, :]

    kw = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
              block_k=block_k, seq_len=s)
    q_spec_blk = pl.BlockSpec((1, 1, block_q, d),
                              lambda bi, hi, qi: (bi, hi, qi, 0))
    kv_spec_full = pl.BlockSpec(
        (1, 1, s, d), lambda bi, hi, qi, g_=group: (bi, hi // g_, 0, 0))
    row_spec_blk = pl.BlockSpec((1, 1, 1, block_q),
                                lambda bi, hi, qi: (bi, hi, 0, qi))
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **kw),
        grid=(b, hq, s // block_q),
        in_specs=[q_spec_blk, kv_spec_full, kv_spec_full, q_spec_blk,
                  row_spec_blk, row_spec_blk],
        out_specs=q_spec_blk,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(q, k, v, g, lse, delta)

    q_spec_full = pl.BlockSpec((1, 1, s, d),
                               lambda bi, hi, kj: (bi, hi, 0, 0))
    kv_spec_blk = pl.BlockSpec(
        (1, 1, block_k, d), lambda bi, hi, kj, g_=group: (bi, hi // g_,
                                                          kj, 0))
    row_spec_full = pl.BlockSpec((1, 1, 1, s),
                                 lambda bi, hi, kj: (bi, hi, 0, 0))
    dkv_out_spec = pl.BlockSpec((1, 1, block_k, d),
                                lambda bi, hi, kj: (bi, hi, kj, 0))
    # dK/dV are produced per Q head ([b, hq, s, d]) and group-summed below:
    # keeping the kernel gridded over Q heads avoids cross-program
    # accumulation; the sum is one XLA reduce over a transient no larger
    # than dQ itself.
    dk_q, dv_q = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **kw),
        grid=(b, hq, s // block_k),
        in_specs=[q_spec_full, kv_spec_blk, kv_spec_blk, q_spec_full,
                  row_spec_full, row_spec_full],
        out_specs=[dkv_out_spec, dkv_out_spec],
        out_shape=[jax.ShapeDtypeStruct((b, hq, s, d), k.dtype),
                   jax.ShapeDtypeStruct((b, hq, s, d), v.dtype)],
        interpret=interpret,
    )(q, k, v, g, lse, delta)
    if group > 1:
        dk = dk_q.reshape(b, hkv, group, s, d).sum(axis=2)
        dv = dv_q.reshape(b, hkv, group, s, d).sum(axis=2)
    else:
        dk, dv = dk_q, dv_q
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention(q, k, v, causal, sm_scale, block_q, block_k,
                     interpret):
    out, _ = _flash_forward(q, k, v, causal, sm_scale, block_q, block_k,
                            interpret)
    return out


def _flash_fwd_rule(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    out, lse = _flash_forward(q, k, v, causal, sm_scale, block_q, block_k,
                              interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, sm_scale, block_q, block_k, interpret,
                    residuals, g):
    q, k, v, out, lse = residuals
    return _flash_backward(q, k, v, out, lse, g, causal, sm_scale,
                           block_q, block_k, interpret)


_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Flash attention. q: [b, hq, s, d]; k/v: [b, hkv, s, d] (GQA).

    `interpret` defaults to True off-TPU so tests run on CPU.

    Traced under a multi-device mesh (``jax.set_mesh``, as the sharded
    train step does) the kernels run per shard inside a ``shard_map``:
    XLA cannot partition a Mosaic kernel itself ("Mosaic kernels cannot
    be automatically partitioned"), and attention is independent per
    (batch, head), so the framework's own layout — batch over the data
    axes, heads over ``tp`` (parallel/sharding.attention_spec) — needs
    no collective. Inside an enclosing shard_map (pipeline stages, ring
    attention) the axes are already manual and the kernel is called as
    is.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != 'tpu'

    def kernel(q_, k_, v_):
        return _flash_attention(q_, k_, v_, causal, sm_scale, block_q,
                                block_k, interpret)

    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1 or mesh.manual_axes:
        return kernel(q, k, v)
    spec = sharding_lib.attention_spec(mesh, q.shape[1], k.shape[1])
    return jax.shard_map(kernel, in_specs=(spec,) * 3, out_specs=spec,
                         check_vma=False)(q, k, v)


def _fit_block(want: int, seq_len: int) -> int:
    """Largest tile <= `want` that DIVIDES seq_len (the kernels require
    it), preferring lane-aligned multiples of 128. seq 768 with a 512
    request fits 384; non-multiple-of-128 seqs fall back to the gcd."""
    import math
    b = min(want, seq_len)
    while b > 128 and seq_len % b:
        b -= 128
    if seq_len % b:
        b = math.gcd(b, seq_len)
    return max(b, 1)


def resolve_impl(impl: str, seq_len: int) -> str:
    """What ``attention(impl=...)`` runs at this sequence length on
    this process's backend: ``'flash'`` or ``'dense'``. 'auto' is flash
    on TPU when the sequence tiles (a multiple of 128, at least 256),
    dense otherwise; entry points log it so a run says which it took."""
    if impl in ('dense', 'flash'):
        return impl
    on_tpu = jax.default_backend() == 'tpu'
    tiles = seq_len % 128 == 0 and seq_len >= 256
    return 'flash' if on_tpu and tiles else 'dense'


def attention(q, k, v, *, causal: bool = True,
              sm_scale: Optional[float] = None,
              impl: str = 'auto',
              block_q: Optional[int] = None,
              block_k: Optional[int] = None) -> jnp.ndarray:
    """Dispatch: 'dense', 'flash', or 'auto' (see ``resolve_impl``).
    block_q/block_k override the flash tile sizes (clamped to seq;
    None → defaults)."""
    s = q.shape[2]
    if resolve_impl(impl, s) == 'dense':
        return dense_attention(q, k, v, causal=causal, sm_scale=sm_scale)
    bq = _fit_block(block_q or DEFAULT_BLOCK_Q, s)
    bk = _fit_block(block_k or DEFAULT_BLOCK_K, s)
    if impl == 'flash' and min(bq, bk) < 128 and s >= 128:
        # The gcd fallback would hand the kernel sub-lane tiles (a
        # pathological grid); explicit flash on such a seq is a
        # user error, not something to quietly degrade.
        raise ValueError(
            f'flash attention needs seq_len divisible by a >=128 '
            f'tile; got seq_len={s} (fitted tiles {bq}x{bk}). Pad '
            f'the sequence or use impl="dense"/"auto".')
    return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                           block_q=bq, block_k=bk)
