"""The Mamba-2 mixer body, shared by the families that have one.

Pure functions of (the block's normed input, the layer's weights, the
layer's state), for a prompt chunk of one sequence (``chunk``) and for
one token of every slot (``decode``). The block around them (which
norm feeds them, what their output is added to) is the family's:
``models/nemotron_h.py`` gives the mixer a block of its own,
``models/falcon_h1.py`` runs it beside attention on one norm's output.

``config`` is the family's configuration, read for ``mamba_heads``,
``mamba_head_dim``, ``d_inner`` (heads x head width, given outright:
never ``expand x hidden``), ``n_groups`` (of B / C and of the gated
norm), ``ssm_state``, ``conv_dim``, ``conv_kernel``, ``chunk_size``
and ``norm_eps``. ``layer`` holds ``w_in [d, 2*d_inner + 2*G*N + H]``
(z | xBC | dt, no bias), ``conv_w [k, conv_dim]``, ``conv_b``,
``dt_bias [H]``, ``a_log [H]``, ``d_skip [H]``, ``gate_norm
[d_inner]``, ``w_out [d_inner, d]``. ``in_mult`` is what a family
multiplies ``W_in``'s output by, element for element (``[in_proj]``
float32; None: nothing).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from skypilot_tpu.ops import mamba2


def in_proj(config, layer, h, in_mult=None):
    """``[z | xBC | dt] = h @ W_in``: z and xBC in the activation
    dtype, dt in float32 with its bias and softplus applied."""
    di, cd = config.d_inner, config.conv_dim
    zxd = jnp.dot(h, layer['w_in'], preferred_element_type=jnp.float32)
    if in_mult is not None:
        zxd = zxd * in_mult
    z, xbc, dt = jnp.split(zxd, [di, di + cd], axis=-1)
    dt = jax.nn.softplus(dt + layer['dt_bias'])
    return z.astype(h.dtype), xbc.astype(h.dtype), dt


def split_xbc(config, xbc):
    """``[.., conv_dim]`` float32 after conv + silu -> x ``[.., H, P]``,
    B and C ``[.., G, N]``."""
    di, gn = config.d_inner, config.n_groups * config.ssm_state
    x, b, c = jnp.split(xbc, [di, di + gn], axis=-1)
    lead = xbc.shape[:-1]
    return (x.reshape(*lead, config.mamba_heads, config.mamba_head_dim),
            b.reshape(*lead, config.n_groups, config.ssm_state),
            c.reshape(*lead, config.n_groups, config.ssm_state))


def gate_out(config, layer, y, z, dtype):
    """``w_norm * group_rmsnorm(y * silu(z))`` then the out projection."""
    g = y * jax.nn.silu(z.astype(jnp.float32))
    lead = g.shape[:-1]
    g = g.reshape(*lead, config.n_groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                          + config.norm_eps)
    g = g.reshape(*lead, -1) * layer['gate_norm'].astype(jnp.float32)
    return jnp.dot(g.astype(dtype), layer['w_out'])


def chunk(config, layer, h, ssm, conv, true_len, in_mult=None):
    """The mixer over one prompt chunk of ONE sequence.

    h: ``[C, d]`` (the block's normed input); ssm ``[H, P, N]`` float32
    and conv ``[k-1, conv_dim]``: the sequence's state before the
    chunk; true_len: valid tokens. Returns (mixer output ``[C, d]``,
    ssm', conv') with the state as it stands after token ``true_len -
    1``: the padded tail advances neither."""
    z, xbc, dt = in_proj(config, layer, h, in_mult)
    window = jnp.concatenate([conv.astype(xbc.dtype), xbc], axis=0)
    xbc_f = mamba2.causal_conv(window, layer['conv_w'], layer['conv_b'])
    conv = jax.lax.dynamic_slice_in_dim(window, true_len,
                                        config.conv_kernel - 1, axis=0)
    valid = jnp.arange(h.shape[0]) < true_len
    dt = jnp.where(valid[:, None], dt, 0.0)       # a step of 0 holds S
    xs, b, c = split_xbc(config, jax.nn.silu(xbc_f))
    y, ssm = mamba2.ssd_chunk_scan(
        xs, dt, -jnp.exp(layer['a_log']), b, c, layer['d_skip'], ssm,
        chunk=config.chunk_size)
    y = y.reshape(h.shape[0], config.d_inner)
    return gate_out(config, layer, y, z, h.dtype), ssm, conv


def decode(config, layer, h, ssm, conv, active, in_mult=None):
    """The mixer for one token of every slot.

    h: ``[slots, d]``; ssm ``[slots, H, P, N]``; conv ``[slots, k-1,
    conv_dim]``; active ``[slots]`` bool. A slot that is not active
    keeps its state bit for bit: the state kernel
    (``mamba2.ssd_decode_live``) moves the active slots' rows of
    ``ssm`` and no others, in place. Its output is garbage the engine
    drops, and finite: the recurrence gives it zeros."""
    z, xbc, dt = in_proj(config, layer, h, in_mult)
    window = jnp.concatenate([conv.astype(xbc.dtype), xbc[:, None]], axis=1)
    xbc_f = (jnp.einsum('skc,kc->sc', window.astype(jnp.float32),
                        layer['conv_w']) + layer['conv_b'])
    xs, b, c = split_xbc(config, jax.nn.silu(xbc_f))
    y, ssm = mamba2.ssd_decode_live(
        xs, dt, -jnp.exp(layer['a_log']), b, c, layer['d_skip'], ssm,
        active)
    conv = jnp.where(active[:, None, None], window[:, 1:], conv)
    y = y.reshape(h.shape[0], config.d_inner)
    return gate_out(config, layer, y, z, h.dtype), ssm, conv
