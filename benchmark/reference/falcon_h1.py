"""Plain reference for the Falcon-H1 block stack, in float32.

Written from the layer equations of ISSUE 33 (the catalog's
``falcon_h1`` config, the family's published modeling code and report,
arXiv:2507.22448) and importing nothing from the program. No kernels,
no cache, no batching: one sequence at a time through one block at a
time, every product at ``highest`` precision, the state-space
recurrence as a plain ``lax.scan`` over time (NOT the chunked SSD
form, so it shares nothing with ``skypilot_tpu/ops/mamba2.py``).

Embedding ``x0 = E[token] * embedding_multiplier``. A block, ``x``
``[T, d]``, no bias anywhere but the convolution's::

    h = rmsnorm(x; norm, eps)
    a = attn(h * attention_in_multiplier) * attention_out_multiplier
          q = h Wq;  k = (h Wk) * key_multiplier;  v = h Wv
          rope on all of q's and k's head, the half-split pairing
          causal softmax(q k^T / sqrt(hd)) v, grouped-query;  a = cat Wo
    s = ssm(h * ssm_in_multiplier) * ssm_out_multiplier
          u = (h W_in) * m        W_in -> [ z | x | B | C | dt ], m the
                                  five ssm_multipliers over those parts
          xBC = silu(causal_depthwise_conv(xBC) + b_conv)  (kernel 4,
                                  w[k-1] on the current step)
          dt = softplus(dt + dt_bias);  A = -exp(A_log)
          head j reads group j // (H / G):
          S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t[g]
          y_t = S_t C_t[g] + D x_t
          y = w * group_rmsnorm(y * silu(z); G groups)
          s = y W_out
    x = x + a + s
    x = x + (silu(g W_gate * mlp_multipliers[0]) * (g W_up)) W_down
            * mlp_multipliers[1],     g = rmsnorm(x; ff_norm)

``logits = rmsnorm(x; final_norm) W_head * lm_head_multiplier``.

Departures from the published model: the weights are the seeded ones
of ``benchmark/weights_falcon_h1.py`` (bfloat16-rounded, read here as
float32); the vocabulary is the configuration's slice.

``act`` selects a control: ``None`` is the reference itself. ``'bf16'``
rounds every tensor that a bfloat16 program rounds (each product's
result once its multiplier is applied, each norm, activation and
residual sum) and keeps in float32 what the configuration's precision
block keeps there: the precision the configuration states, which has
to pass. ``'bf16-w8a8'`` goes one step below it: every matrix in int8
(the caller applies ``quantize_weights``; one absmax scale an output
channel) and every weight product's left input in int8, one absmax
scale a token row. ``MECHANISMS`` each leave out, in float32, one
thing the configuration adds: what a program that dropped it would
serve.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
MECHANISMS = ('no-attn-branch', 'no-ssm-branch', 'no-mup',
              'no-key-multiplier', 'no-conv-bias', 'no-gate-norm')
ACTS = (None, 'bf16', 'bf16-w8a8', *MECHANISMS)
MULTIPLIERS = ('embedding_multiplier', 'lm_head_multiplier',
               'attention_in_multiplier', 'attention_out_multiplier',
               'key_multiplier', 'ssm_in_multiplier', 'ssm_out_multiplier')


def bf16(x: jnp.ndarray) -> jnp.ndarray:
    return x.astype(jnp.bfloat16).astype(F32)


def int8_rows(x: jnp.ndarray) -> jnp.ndarray:
    scale = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True) / 127.0, 1e-12)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def matmul(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def hooks(act: Optional[str]) -> Tuple[Callable, Callable]:
    """``act`` as (r: every tensor a bfloat16 program rounds, mm: a
    weight product, its float32 result not yet rounded)."""
    if act not in ACTS:
        raise ValueError(f'unknown control {act!r}')
    same = (lambda t: t)
    r = same if act is None or act in MECHANISMS else bf16
    fed = int8_rows if act == 'bf16-w8a8' else same
    return r, (lambda x, w: matmul(fed(x), w))


def weights_int8(act: Optional[str]) -> bool:
    return act == 'bf16-w8a8'


def mup(cfg: Dict[str, Any], act: Optional[str]) -> Dict[str, Any]:
    """The eleven multipliers as the computation ``act`` applies them:
    all 1 for ``no-mup``, the key's 1 for ``no-key-multiplier``."""
    out = {k: float(cfg[k]) for k in MULTIPLIERS}
    out['ssm_multipliers'] = [float(v) for v in cfg['ssm_multipliers']]
    out['mlp_multipliers'] = [float(v) for v in cfg['mlp_multipliers']]
    if act == 'no-mup':
        out = {k: ([1.0] * len(v) if isinstance(v, list) else 1.0)
               for k, v in out.items()}
    if act == 'no-key-multiplier':
        out['key_multiplier'] = 1.0
    return out


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def quantize_weights(w: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    """Every matrix of a block as int8 would hold it, one absmax scale
    an output channel; the convolution's taps stay (the precision
    block keeps them in float32)."""
    def q(v):
        scale = jnp.maximum(jnp.max(jnp.abs(v), axis=-2, keepdims=True)
                            / 127.0, 1e-12)
        return jnp.clip(jnp.round(v / scale), -127, 127) * scale
    return {k: q(v) if v.ndim >= 2 and k != 'conv_w' else v
            for k, v in w.items()}


def rope(x, theta: float):
    """Rotary embedding of ``x [T, heads, hd]`` at positions 0..T-1:
    channel ``i`` pairs with ``i + hd/2`` (the half-split pairing)."""
    T, _, hd = x.shape
    inv = 1.0 / (F32(theta) ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def attn_branch(cfg, w, h, act: Optional[str] = None):
    r, mm = hooks(act)
    m = mup(cfg, act)
    T = h.shape[0]
    hq, hkv, hd = (cfg['num_attention_heads'], cfg['num_key_value_heads'],
                   cfg['head_dim'])
    h = r(h * m['attention_in_multiplier'])
    q = r(mm(h, w['wq'])).reshape(T, hq, hd)
    k = r(mm(h, w['wk']) * m['key_multiplier']).reshape(T, hkv, hd)
    v = r(mm(h, w['wv'])).reshape(T, hkv, hd)
    q, k = r(rope(q, cfg['rope_theta'])), r(rope(k, cfg['rope_theta']))
    k, v = jnp.repeat(k, hq // hkv, 1), jnp.repeat(v, hq // hkv, 1)
    scores = jnp.einsum('qhd,khd->hqk', q, k, precision=HIGHEST) * hd ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], scores,
                       -jnp.inf)
    att = jnp.einsum('hqk,khd->qhd', jax.nn.softmax(scores, -1), v,
                     precision=HIGHEST)
    return mm(r(att.reshape(T, hq * hd)), w['wo']) \
        * m['attention_out_multiplier']


def ssm_branch(cfg, w, h, act: Optional[str] = None):
    r, mm = hooks(act)
    m = mup(cfg, act)
    T = h.shape[0]
    H, P = cfg['mamba_n_heads'], cfg['mamba_d_head']
    G, N, K = cfg['mamba_n_groups'], cfg['mamba_d_state'], cfg['mamba_d_conv']
    di, gn = cfg['mamba_d_ssm'], G * N
    seg = jnp.concatenate([jnp.full((n,), v, F32) for n, v in zip(
        (di, di, gn, gn, H), m['ssm_multipliers'])])
    u = mm(r(h * m['ssm_in_multiplier']), w['w_in']) * seg[None]
    z, xbc, dt = jnp.split(u, [di, 2 * di + 2 * gn], -1)
    z, xbc = r(z), r(xbc)            # dt stays float32 in the program
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])
    conv = sum(padded[j:j + T] * w['conv_w'][j][None] for j in range(K))
    bias = 0.0 if act == 'no-conv-bias' else w['conv_b'][None]
    xbc = jax.nn.silu(conv + bias)
    x, b, c = jnp.split(xbc, [di, di + gn], -1)
    x = x.reshape(T, H, P)
    b = jnp.repeat(b.reshape(T, G, N), H // G, axis=1)      # [T, H, N]
    c = jnp.repeat(c.reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + w['dt_bias'][None])           # [T, H]
    a = -jnp.exp(w['a_log'])

    def step(s, xs):
        x_t, b_t, c_t, dt_t = xs
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return s, jnp.sum(s * c_t[:, None, :], -1) + w['d_skip'][:, None] * x_t
    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32), (x, b, c, dt))
    g = y.reshape(T, di) * jax.nn.silu(z)
    if act != 'no-gate-norm':
        g = g.reshape(T, G, di // G)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                              + cfg['rms_norm_eps'])
        g = g.reshape(T, di) * w['gate_norm']
    return r(mm(r(g), w['w_out'])) * m['ssm_out_multiplier']


def mlp(cfg, w, x, act: Optional[str] = None):
    r, mm = hooks(act)
    m = mup(cfg, act)['mlp_multipliers']
    g = r(rms_norm(x, w['ff_norm'], cfg['rms_norm_eps']))
    inner = r(jax.nn.silu(mm(g, w['w_gate']) * m[0]) * mm(g, w['w_up']))
    return mm(inner, w['w_down']) * m[1]


def layer_forward(cfg: Dict[str, Any], w: Dict[str, jnp.ndarray],
                  x: jnp.ndarray, act: Optional[str] = None) -> jnp.ndarray:
    """One block on one sequence. x ``[seq, hidden]`` float32; ``w``:
    the block's leaves as float32 (already int8-rounded where
    ``weights_int8(act)``)."""
    r = hooks(act)[0]
    h = r(rms_norm(x, w['norm'], cfg['rms_norm_eps']))
    a = 0.0 if act == 'no-attn-branch' else attn_branch(cfg, w, h, act)
    s = 0.0 if act == 'no-ssm-branch' else ssm_branch(cfg, w, h, act)
    x = r(x + a + s)
    return r(x + mlp(cfg, w, x, act))


def branch_rms(cfg, w, x) -> Dict[str, jnp.ndarray]:
    """RMS of the stream into a block and of the three terms the block
    adds to it (``weights_falcon_h1.py`` balances them)."""
    h = rms_norm(x, w['norm'], cfg['rms_norm_eps'])
    a, s = attn_branch(cfg, w, h), ssm_branch(cfg, w, h)
    mid = x + a + s
    rms = (lambda t: jnp.sqrt(jnp.mean(t * t)))
    return {'stream': rms(x), 'attn': rms(a), 'ssm': rms(s),
            'mlp': rms(mlp(cfg, w, mid))}


def embed(cfg, table, tokens, act: Optional[str] = None):
    return hooks(act)[0](table[tokens] * mup(cfg, act)['embedding_multiplier'])


def head(cfg, final_norm, lm_head, x, act: Optional[str] = None):
    """Float32 logits of the rows given, over the vocabulary slice."""
    r, mm = hooks(act)
    h = r(rms_norm(x, final_norm, cfg['rms_norm_eps']))
    return mm(h, lm_head) * mup(cfg, act)['lm_head_multiplier']


def forward(cfg: Dict[str, Any], weights: Dict[str, Any], tokens,
            act: Optional[str] = None) -> jnp.ndarray:
    """Whole forward pass of one sequence, for tests at small sizes.
    ``weights``: ``{'embed', 'layers': [leaves], 'final_norm',
    'lm_head'}``, float32. Returns logits ``[seq, vocab]``."""
    x = embed(cfg, weights['embed'], tokens, act)
    for w in weights['layers']:
        if weights_int8(act):
            w = quantize_weights(w)
        x = layer_forward(cfg, w, x, act)
    return head(cfg, weights['final_norm'], weights['lm_head'], x, act)
