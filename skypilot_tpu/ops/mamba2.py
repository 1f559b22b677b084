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

Those two are plain ``jax.numpy``. What a decode step RUNS is
``ssd_decode_live``, a Pallas kernel (``_decode_state_kernel``, in a
trace ``ssd_decode_state``): ``ssd_decode_step``'s line for the slots
that are active, and nothing for the others. The plain form reads and
writes the state of every slot, 4 MB a slot and block at Falcon-H1's
``H, P, N = 32, 128, 256`` and 2 MB at Nemotron's ``64, 64, 128``, and
a traced step was mostly that (PERF.md section 6, PR 34). The kernel is
ONE grid step in the way of ``paged_attention._decode_kernel``: the
state array stays in HBM, aliased onto the kernel's own result, and the
kernel walks the slots, so that a slot that is not active costs a
scalar compare and keeps its state bit for bit, nothing having touched
it. A live slot's state moves in tiles of ``[hb, P, N]`` (``hb`` from
the call's shapes under a VMEM budget, `_state_tile_heads`): one copy
in, into one of two buffers, while the tile before is worked; the
update into one of two more; one copy back while the next is worked.
The small operands sit whole in VMEM and ``exp(dt * A)`` in SMEM, a
scalar a head. The arithmetic is elementwise on the VPU, float32: the
state's rows have the head's width ``P`` on the sublanes and the state
``N`` on the lanes, so ``dt * x`` is handed in transposed (``[S, P,
H]``: a head's column broadcasts along the lanes) and ``S . C`` comes
out the same way, a lane reduction a row. ``ssd_decode_step`` stays as the
kernel's ground truth (tests/unit_tests/test_ssm_decode_kernel.py).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


# What the state kernel's four tile buffers (two in flight in, two out)
# may hold in VMEM, and the limit the call asks for.
_STATE_VMEM_BUDGET = 8 << 20
_STATE_VMEM_LIMIT = 48 << 20


def _state_tile_heads(n_heads: int, head_dim: int, n_state: int) -> int:
    """Heads a tile of the state kernel, from the call's shapes: the
    most that divide ``n_heads`` and fit `_STATE_VMEM_BUDGET` four
    times over."""
    hb = max(1, min(n_heads,
                    _STATE_VMEM_BUDGET // (4 * head_dim * n_state * 4)))
    while n_heads % hb:
        hb -= 1
    return hb


def _decode_state_kernel(active_ref, decay_ref, xdt_ref, b_ref, c_ref,
                         state_hbm, sc_ref, state_out, in_buf, out_buf,
                         sems, *, hb: int):
    """The recurrence for one token of every LIVE slot, in one grid
    step: the state stays in HBM (``state_out`` is ``state_hbm``, the
    same bytes) and the kernel walks the slots itself, so a slot that
    is not active costs a scalar compare and its state is not touched.

    A live slot's state moves in tiles of ``[hb, P, N]``: copied into
    one of two buffers while the tile before is worked, updated into
    one of two more, copied back while the next is worked; after a
    slot's last tile the next live slot's first is already in flight.
    xdt_ref ``[slots, P, H]`` is ``dt * x`` with the head's width on
    the sublanes, where the state has it. sc_ref, the same shape, takes
    ``S . C`` of the state as it was BEFORE the step: the step's output
    is ``decay * (S . C) + dt x (B . C)``, whose second term needs no
    state, and a reduction that does not wait for the update keeps the
    head loop under the copies' time at a 128-wide state (0.437 against
    0.472 ms for 64 live slots; PERF.md section 6, PR 34)."""
    slots, _, n_heads = xdt_ref.shape
    tiles = n_heads // hb
    per_group = n_heads // b_ref.shape[1]

    def next_live(b):
        return jax.lax.while_loop(
            lambda b: jnp.logical_and(
                b < slots, active_ref[jnp.minimum(b, slots - 1)] == 0),
            lambda b: b + 1, b)

    def fetch(b, t, buf):
        return pltpu.make_async_copy(
            state_hbm.at[b, pl.ds(t * hb, hb)], in_buf.at[buf],
            sems.at[0, buf])

    def store(b, t, buf):
        return pltpu.make_async_copy(
            out_buf.at[buf], state_out.at[b, pl.ds(t * hb, hb)],
            sems.at[1, buf])

    # A slot that is not live reads zeros, not what VMEM held.
    sc_ref[...] = jnp.zeros_like(sc_ref)

    def slot(carry):
        b, worked = carry                    # tiles worked before b's
        following = next_live(b + 1)
        for t in range(tiles):
            buf = (worked + t) % 2
            if t + 1 < tiles:
                fetch(b, t + 1, 1 - buf).start()
            else:
                pl.when(following < slots)(
                    lambda: fetch(following, 0, 1 - buf).start())
            fetch(b, t, buf).wait()
            # The tile this buffer held two tiles ago has to be out.
            pl.when(worked + t >= 2)(lambda: store(b, t, buf).wait())
            for j in range(hb):
                h = t * hb + j
                g = h // per_group
                old = in_buf[buf, j]                          # [P, N]
                out_buf[buf, j] = (
                    old * decay_ref[b * n_heads + h]
                    + xdt_ref[b, :, h:h + 1] * b_ref[b, g:g + 1, :])
                sc_ref[b, :, h:h + 1] = jnp.sum(
                    old * c_ref[b, g:g + 1, :], axis=-1, keepdims=True)
            store(b, t, buf).start()
        return following, worked + tiles

    first = next_live(0)
    pl.when(first < slots)(lambda: fetch(first, 0, 0).start())
    _, worked = jax.lax.while_loop(lambda carry: carry[0] < slots, slot,
                                   (first, 0))
    for back in (1, 2):                      # the copies still out
        pl.when(worked >= back)(
            lambda: store(0, 0, (worked - back) % 2).wait())


def ssd_decode_live(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
                    b: jnp.ndarray, c: jnp.ndarray, d_skip: jnp.ndarray,
                    state: jnp.ndarray, active: jnp.ndarray, *,
                    interpret: Optional[bool] = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``ssd_decode_step`` for the slots that are ``active`` ``[S]``
    bool, as a Pallas kernel that moves only their state: the state
    array is aliased onto the result, a live slot's rows are copied in,
    advanced and copied back, and **a slot that is not active keeps its
    state bit for bit because nothing touches it**; its ``y`` is zeros.
    Nothing of a dead slot's inputs is read by a live one. Float32
    throughout, the products elementwise. Compiled on a TPU,
    interpreted elsewhere."""
    if interpret is None:
        interpret = jax.default_backend() != 'tpu'
    return _decode_live(x, dt, a, b, c, d_skip, state, active,
                        interpret=interpret)


# Jitted so that the blocks of a step program share ONE trace and one
# lowering of the kernel (the head loop is unrolled: 32 or 64 bodies),
# where each call of its own cost the hybrid's decode program 2.2 s more
# to lower than its parent's 1.5 s.
@functools.partial(jax.jit, static_argnames=('interpret',))
def _decode_live(x, dt, a, b, c, d_skip, state, active, *, interpret):
    slots, n_heads, head_dim = x.shape
    n_state = state.shape[-1]
    hb = _state_tile_heads(n_heads, head_dim, n_state)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    tile = pltpu.VMEM((2, hb, head_dim, n_state), jnp.float32)
    decay = jnp.exp(dt * a[None, :])                      # [S, H]
    # The head's width on the sublanes, as the state's rows have it.
    xdt = jnp.swapaxes(dt[:, :, None] * x, 1, 2)          # [S, P, H]
    sc, state = pl.pallas_call(
        functools.partial(_decode_state_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[whole, whole, whole, in_hbm],
            out_specs=[whole, in_hbm],
            scratch_shapes=[tile, tile, pltpu.SemaphoreType.DMA((2, 2))],
        ),
        out_shape=[jax.ShapeDtypeStruct(xdt.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_STATE_VMEM_LIMIT),
        interpret=interpret,
        name='ssd_decode_state',
    )(active.astype(jnp.int32), decay.reshape(-1), xdt, b, c, state)
    # y = S' . C + D x with S' = decay S + (dt x) (x) B.
    bc = jnp.repeat(jnp.sum(b * c, axis=-1), n_heads // b.shape[1], axis=1)
    y = (decay[:, :, None] * jnp.swapaxes(sc, 1, 2)
         + (dt * bc + d_skip[None, :])[:, :, None] * x)
    return jnp.where(active[:, None, None], y, 0.0), state


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
