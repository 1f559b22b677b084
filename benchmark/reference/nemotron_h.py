"""Plain reference for the Nemotron-H hybrid block stack, in float32.

Written from the layer equations of ISSUE 27 (the catalog's
``nemotron_h`` config and the family's published description) and
importing nothing from the program. No kernels, no cache, no batching:
one sequence at a time through one block at a time, every product at
``highest`` precision, the state-space recurrence as a plain
``lax.scan`` over time (NOT a chunked scan).

Every block is ``x = x + mixer(rmsnorm(x, w, eps))``:

- ``M`` (Mamba-2): ``[z | xBC | dt] = h @ W_in``; ``xBC = silu(conv(xBC)
  + b_conv)`` (causal, depthwise, kernel 4, ``w[k-1]`` on the current
  step); ``[x | B | C] = xBC``; ``dt = softplus(dt + dt_bias)``; ``A =
  -exp(A_log)``; per head ``h`` of group ``g = h // (H / G)``: ``S_t =
  exp(dt_t A) S_{t-1} + dt_t x_t B_t[g]^T``, ``y_t = S_t C_t[g] + D
  x_t``; ``y = w_norm * group_rmsnorm(y * silu(z), G groups)``; out ``y @
  W_out``.
- ``*`` (attention): grouped-query causal softmax attention, no bias,
  NO positional embedding (ISSUE 27's reading of the family's
  published modeling code; the configuration file lists it under
  ``assumed``).
- ``E`` (experts): ``s = sigmoid(h @ W_r)``; the top-``k`` of ``s +
  b_corr``; ``w = s[chosen] / (sum(s[chosen]) + 1e-20) * scale``; out
  ``sum w_e relu(h @ U_e)**2 @ D_e + shared(h)`` with the sum over the
  chosen experts THIS SHARE HOLDS (``w`` normalised over all ``k``).

Departures from the published model: the weights are the seeded ones
of ``benchmark/weights_nemotron_h.py`` (bfloat16-rounded, read here as
float32; the up projections come stored ``[held, f, d]`` and are
turned back); the share (experts held, vocabulary slice) is the
configuration's.

``act`` selects a control, computed in a lower precision than the
reference: ``None`` is the reference itself. ``'bf16'`` rounds every
tensor that a bfloat16 program rounds (each product's inputs and
result, each norm, activation and residual sum) and keeps in float32
what the configuration's precision block keeps there: that is the
precision the configuration states, and it has to pass. The controls
proper go one step below it: ``'bf16-state'`` also holds the router's
product and scores, ``dt`` / ``A`` / the decay and the SSM state (after
every step) in bfloat16; ``'bf16-w8'`` has every matrix in int8 (the
caller applies ``quantize_weights``; one absmax scale an output
channel); ``'bf16-w8a8'`` also feeds every weight product its left
input in int8, one absmax scale a token row. Two more controls leave
out a mechanism, in float32: ``'no-router-bias'`` chooses the experts
without the correction bias, ``'no-conv-bias'`` convolves without
``b_conv``: what a program that dropped either would serve.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
MECHANISMS = ('no-router-bias', 'no-conv-bias')
ACTS = (None, 'bf16', 'bf16-state', 'bf16-w8', 'bf16-w8a8', *MECHANISMS)


def bf16(x: jnp.ndarray) -> jnp.ndarray:
    return x.astype(jnp.bfloat16).astype(F32)


def int8_rows(x: jnp.ndarray) -> jnp.ndarray:
    scale = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True) / 127.0, 1e-12)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def matmul(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def hooks(act: Optional[str]) -> Tuple[Callable, Callable, Callable]:
    """``act`` as (r: every tensor a bfloat16 program rounds, mm: a
    weight product, low: what the precision block keeps in float32)."""
    if act not in ACTS:
        raise ValueError(f'unknown control {act!r}')
    same = (lambda t: t)
    r = same if act is None or act in MECHANISMS else bf16
    low = bf16 if act == 'bf16-state' else same
    fed = int8_rows if act == 'bf16-w8a8' else same
    return r, (lambda x, w: r(matmul(fed(x), w))), low


def weights_int8(act: Optional[str]) -> bool:
    return act in ('bf16-w8', 'bf16-w8a8')


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def quantize_weights(w: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    """Every matrix of a block (two or more axes; the router stays: the
    precision block keeps it in float32) as int8 would hold it, one
    absmax scale an output channel (of the ``[held, f, d]`` expert
    stacks: over their middle axis)."""
    def q(v):
        scale = jnp.maximum(jnp.max(jnp.abs(v), axis=-2, keepdims=True)
                            / 127.0, 1e-12)
        return jnp.clip(jnp.round(v / scale), -127, 127) * scale
    return {k: q(v) if v.ndim >= 2 and k not in ('router', 'conv_w') else v
            for k, v in w.items()}


def mamba_mixer(cfg, w, h, act: Optional[str] = None):
    r, mm, low = hooks(act)
    T = h.shape[0]
    H, P = cfg['mamba_num_heads'], cfg['mamba_head_dim']
    G, N, K = cfg['n_groups'], cfg['ssm_state_size'], cfg['conv_kernel']
    di, gn = H * P, G * N
    fed = int8_rows if act == 'bf16-w8a8' else (lambda t: t)
    z, xbc, dt = jnp.split(matmul(fed(h), w['w_in']),
                           [di, 2 * di + 2 * gn], -1)
    z, xbc = r(z), r(xbc)            # dt stays float32 in the program
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])
    conv = sum(padded[j:j + T] * w['conv_w'][j][None] for j in range(K))
    bias = 0.0 if act == 'no-conv-bias' else w['conv_b'][None]
    xbc = jax.nn.silu(conv + bias)
    x, b, c = jnp.split(xbc, [di, di + gn], -1)
    x = x.reshape(T, H, P)
    b = jnp.repeat(b.reshape(T, G, N), H // G, axis=1)      # [T, H, N]
    c = jnp.repeat(c.reshape(T, G, N), H // G, axis=1)
    dt = low(jax.nn.softplus(dt + w['dt_bias'][None]))      # [T, H]
    a = low(-jnp.exp(w['a_log']))

    def step(s, xs):
        x_t, b_t, c_t, dt_t = xs
        s = low(low(jnp.exp(dt_t * a))[:, None, None] * s
                + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return s, jnp.sum(s * c_t[:, None, :], -1) + w['d_skip'][:, None] * x_t
    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32), (x, b, c, dt))
    g = (y.reshape(T, di) * jax.nn.silu(z)).reshape(T, G, di // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                          + cfg['layer_norm_epsilon'])
    return mm(r(g.reshape(T, di) * w['gate_norm']), w['w_out'])


def attn_mixer(cfg, w, h, act: Optional[str] = None):
    r, mm, _ = hooks(act)
    T = h.shape[0]
    hq, hkv, hd = (cfg['num_attention_heads'], cfg['num_key_value_heads'],
                   cfg['head_dim'])
    q = mm(h, w['wq']).reshape(T, hq, hd)
    k = jnp.repeat(mm(h, w['wk']).reshape(T, hkv, hd), hq // hkv, 1)
    v = jnp.repeat(mm(h, w['wv']).reshape(T, hkv, hd), hq // hkv, 1)
    scores = jnp.einsum('qhd,khd->hqk', q, k, precision=HIGHEST) * hd ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], scores,
                       -jnp.inf)
    att = jnp.einsum('hqk,khd->qhd', jax.nn.softmax(scores, -1), v,
                     precision=HIGHEST)
    return mm(r(att.reshape(T, hq * hd)), w['wo'])


def route(cfg, w, h, act: Optional[str] = None):
    """(chosen ids ``[T, k]``, weights ``[T, k]``) over ALL experts."""
    low = hooks(act)[2]
    s = low(jax.nn.sigmoid(low(matmul(low(h), low(w['router'])))))
    bias = 0.0 if act == 'no-router-bias' else w['router_bias'][None]
    _, idx = jax.lax.top_k(s + bias, cfg['num_experts_per_tok'])
    chosen = jnp.take_along_axis(s, idx, -1)
    return idx, (chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)
                 * cfg['routed_scaling_factor'])


def experts_part(cfg, w, h, idx, weights, act: Optional[str] = None):
    """The held experts' part: each in turn, on every token, weighted
    by what the router gave it there (0 where it was not chosen)."""
    r, mm, _ = hooks(act)
    first = cfg.get('expert_offset', 0)

    def one(out, expert):
        e, up, down = expert
        gate = jnp.sum(jnp.where(idx == first + e, weights, 0.0), -1)
        inner = r(jnp.square(jax.nn.relu(mm(h, up.T))))
        return out + gate[:, None] * mm(inner, down), None
    # A scan, not a Python loop: one expert's body is compiled once
    # (64 unrolled copies took minutes to compile at each padded length).
    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        jnp.arange(w['w_up'].shape[0]), w['w_up'], w['w_down']))
    return out


def shared_part(w, h, act: Optional[str] = None):
    r, mm, _ = hooks(act)
    return mm(r(jnp.square(jax.nn.relu(mm(h, w['shared_up'])))),
              w['shared_down'])


def moe_mixer(cfg, w, h, act: Optional[str] = None):
    idx, weights = route(cfg, w, h, act)
    return (experts_part(cfg, w, h, idx, weights, act)
            + shared_part(w, h, act))


def layer_forward(cfg: Dict[str, Any], kind: str, w: Dict[str, jnp.ndarray],
                  x: jnp.ndarray, act: Optional[str] = None) -> jnp.ndarray:
    """One block of ``kind`` on one sequence. x ``[seq, hidden]``
    float32; ``w``: the block's leaves as float32 (already int8-rounded
    where ``weights_int8(act)``)."""
    r = hooks(act)[0]
    h = r(rms_norm(x, w['norm'], cfg['layer_norm_epsilon']))
    if kind == 'M':
        return r(x + mamba_mixer(cfg, w, h, act))
    if kind == '*':
        return r(x + attn_mixer(cfg, w, h, act))
    return r(x + r(moe_mixer(cfg, w, h, act)))


def router_flips(cfg, w, x) -> jnp.ndarray:
    """Per token of an ``E`` block: whether rounding the router's input
    to bfloat16 (what a bfloat16 program feeds its float32 router)
    changes the set of chosen experts."""
    h = rms_norm(x, w['norm'], cfg['layer_norm_epsilon'])
    a, _ = route(cfg, w, h)
    b, _ = route(cfg, w, bf16(h))
    return jnp.any(jnp.sort(a, -1) != jnp.sort(b, -1), axis=-1)


def router_margin(cfg, w, x) -> jnp.ndarray:
    """Per token of an ``E`` block: how far the router's choice is from
    a tie, in units of what rounding its input to bfloat16 moves it.

    The choice changes when the last expert chosen (``a``: the
    ``k``-th largest of ``s + b_corr``) and the first one left out
    (``b``) swap places. Their distance ``m`` moves with the input
    ``h`` by ``h . (s_a (1 - s_a) W_r[:, a] - s_b (1 - s_b) W_r[:,
    b])``; rounding every element of ``h`` to bfloat16 (a uniform
    error of at most ``2**-9`` of the element) gives that a standard
    deviation ``sigma``. Returns ``m / sigma``: about 2 and under, one
    rounding of the input flips the choice; a bfloat16 program, whose
    residual stream was rounded after every block before this one,
    flips it up to some tens (PERF.md section 2 has the reading)."""
    k = cfg['num_experts_per_tok']
    h = rms_norm(x, w['norm'], cfg['layer_norm_epsilon'])
    s = jax.nn.sigmoid(matmul(h, w['router']))
    top, idx = jax.lax.top_k(s + w['router_bias'][None], k + 1)
    slope = s * (1.0 - s)

    def pull(i):                                   # [T, hidden]
        at = idx[:, i]
        return (jnp.take_along_axis(slope, at[:, None], 1)
                * w['router'][:, at].T)
    moved = h * (pull(k - 1) - pull(k))
    sigma = jnp.sqrt(jnp.sum(moved * moved, -1)) * 2.0 ** -9 / 3.0 ** 0.5
    return (top[:, k - 1] - top[:, k]) / jnp.maximum(sigma, 1e-30)


def embed(table, tokens):
    return table[tokens]


def head(cfg, final_norm, lm_head, x, act: Optional[str] = None):
    """Float32 logits of the rows given, over the vocabulary slice."""
    r, _, _ = hooks(act)
    fed = int8_rows if act == 'bf16-w8a8' else (lambda t: t)
    h = r(rms_norm(x, final_norm, cfg['layer_norm_epsilon']))
    return matmul(fed(h), lm_head)


def forward(cfg: Dict[str, Any], weights: Dict[str, Any], tokens,
            act: Optional[str] = None) -> jnp.ndarray:
    """Whole forward pass of one sequence, for tests at small sizes.
    ``weights``: ``{'embed', 'layers': [(kind, leaves)], 'final_norm',
    'lm_head'}``, float32. Returns logits ``[seq, vocab]``."""
    x = embed(weights['embed'], tokens)
    for kind, w in weights['layers']:
        if weights_int8(act):
            w = quantize_weights(w)
        x = layer_forward(cfg, kind, w, x, act)
    return head(cfg, weights['final_norm'], weights['lm_head'], x, act)
