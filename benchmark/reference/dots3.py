"""Plain reference for the dots3-note block stack, in float32.

Written from the layer equations of ISSUE 31 (the catalog's
``dots3_note`` config; the indexer's key names and form are
DeepSeek-V3.2-Exp's) and importing nothing from the program. No
kernels, no cache, no batching, no absorbed products: one sequence at a
time through one block at a time, every product at ``highest``
precision, keys and values up-projected per head as the equations are
written. Attention runs over query blocks (``QUERY_BLOCK``) and head
groups (``HEAD_GROUP``) so that 32k positions fit a chip's memory.

With ``u = rmsnorm(x)`` and a plain residual around each half:

- ``full``: ``c_q = a_q rmsnorm(u W_dq)``; per head ``[q_nope | q_rope]
  = c_q W_uq[h]``, rope on ``q_rope``; ``[c_raw | k_rope_raw] = u
  W_dkv``, ``c_kv = a_kv rmsnorm(c_raw)``, ``k_rope = rope(k_rope_raw)``
  (one for all heads); ``k_nope_h = c_kv W_uk[h]^T``, ``v_h = c_kv
  W_uv[h]``. Indexer: ``qI_j = c_q W_qI[j]``, ``kI = layernorm(u
  W_kI)``, rope on the first ``qk_rope_head_dim`` columns of both, ``w
  = u W_w``; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``; ``S_t``
  = the ``index_topk`` positions ``s <= t`` of largest ``I[t, s]``
  (``lax.top_k``: the lower position wins a tie), all of them while
  ``t < index_topk``. ``score_h = (q_nope_h . k_nope_h[s] + q_rope_h .
  k_rope[s]) / sqrt(nope + rope)``, softmax over ``S_t``, ``o_h = sum
  p v_h[s]``; ``g = sigmoid(u W_g)``, ``o_h <- g_h o_h``; out
  ``concat(o) W_o``.
- ``sliding``: the same form with the ``swa_*`` sizes over the keys
  ``t - window < s <= t`` (the window counts the query's own token),
  no indexer.
- FFN: block ``i < first_k_dense_replace`` is ``(silu(h G) * (h U))
  D``; the others route (``s = sigmoid(h W_r)``, the top-``k`` of ``s +
  b_corr``, ``w = s[chosen] / (sum + 1e-20) * scale``) over ALL experts
  and sum the chosen experts THIS SHARE HOLDS, each gated as above,
  plus the shared expert.

Departures from the published model: the weights are the seeded ones of
``benchmark/weights_dots3.py`` (bfloat16-rounded, read here as float32;
``W_uk`` / ``W_uv`` come stored per head and the expert stacks ``[held,
f, d]``); the share (experts held, vocabulary slice) is the
configuration's; the vision and audio towers and the multi-token
prediction module are not in the catalog's ``config`` and are left out;
the indexer's Hadamard rotation and FP8 are left out (an orthogonal map
of both sides leaves every score as it is). The three readings the
configuration lists under ``assumed`` are one function each:
``rescale`` (``apply_mla_qkv_lora_rescale``), ``head_gate``
(``attention_gate_type: headwise``), ``rope_columns``.

``act`` selects a control: ``None`` is the reference itself.
``'bf16'`` rounds every tensor that a bfloat16 program rounds (each
product's inputs and result, each norm, activation and residual sum,
the cached rows and indexer keys) and keeps in float32 what the
configuration's precision block keeps there (router, index scores and
their top-k, softmax, logits): the stated precision, which has to pass.
One step below: ``'bf16-low'`` also holds the router's and the
indexer's scores and the softmax in bfloat16; ``'bf16-w8'`` has every
matrix in int8 (one absmax scale an output channel); ``'bf16-w8a8'``
also feeds every weight product its left input in int8, one scale a
token row. ``MECHANISMS`` each drop one thing, in float32:
``'no-index'`` attends to all of ``s <= t``, ``'no-window'`` lets a
sliding block see all of ``s <= t``, ``'no-gate'`` leaves out the head
gate, ``'no-rescale'`` sets ``a_q = a_kv = 1``, ``'no-router-bias'``
chooses the experts without the correction bias.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
MECHANISMS = ('no-index', 'no-window', 'no-gate', 'no-rescale',
              'no-router-bias')
ACTS = (None, 'bf16', 'bf16-low', 'bf16-w8', 'bf16-w8a8', *MECHANISMS)
QUERY_BLOCK = 256
HEAD_GROUP = 16
_NEG = -jnp.inf


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
    low = bf16 if act == 'bf16-low' else same
    fed = int8_rows if act == 'bf16-w8a8' else same
    return r, (lambda x, w: r(matmul(fed(x), w))), low


def weights_int8(act: Optional[str]) -> bool:
    return act in ('bf16-w8', 'bf16-w8a8')


def quantize_weights(w: Any) -> Any:
    """Every matrix of a block (two or more axes; the router stays: the
    precision block keeps it in float32) as int8 would hold it, one
    absmax scale an output channel (over the axis before the last)."""
    def q(path, v):
        name = path[-1].key
        if v.ndim < 2 or name == 'router':
            return v
        scale = jnp.maximum(jnp.max(jnp.abs(v), axis=-2, keepdims=True)
                            / 127.0, 1e-12)
        return jnp.clip(jnp.round(v / scale), -127, 127) * scale
    return jax.tree_util.tree_map_with_path(q, w)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def layer_norm(x, weight, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * weight + bias


def rope(x, positions, theta):
    """Rotate ``x [T, ..., dim]`` at ``positions [T]``: column ``i``
    with column ``i + dim / 2`` (a relabelling of seeded columns
    against the interleaved convention)."""
    dim = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (dim // 2,)
    c, s = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


# ---------------------------------------------------------------------------
# the three readings, one function each

def rescale(cfg, q_rank: int, kv_rank: int, act=None) -> Tuple[float, float]:
    """``apply_mla_qkv_lora_rescale``: (a_q, a_kv) = ``sqrt(hidden /
    rank)`` each, after the latents' norms."""
    if not cfg['apply_mla_qkv_lora_rescale'] or act == 'no-rescale':
        return 1.0, 1.0
    d = cfg['hidden_size']
    return (d / q_rank) ** 0.5, (d / kv_rank) ** 0.5


def head_gate(w, u, act=None):
    """``attention_gate_type: headwise``: ``sigmoid(u W_g)``, one
    scalar a head, on the heads' outputs before ``W_o``."""
    if act == 'no-gate':
        return jnp.ones((u.shape[0], w['w_gate'].shape[1]), F32)
    return jax.nn.sigmoid(matmul(u, w['w_gate']))


def rope_columns(cfg) -> int:
    """How many of the indexer's columns are rotated: the first
    ``qk_rope_head_dim``."""
    return min(cfg['qk_rope_head_dim'], cfg['index_head_dim'])


def attn_sizes(cfg, kind: str) -> Dict[str, Any]:
    p = '' if kind == 'full' else 'swa_'
    return {'heads': cfg[p + 'num_attention_heads'],
            'q_rank': cfg[p + 'q_lora_rank'],
            'kv_rank': cfg[p + 'kv_lora_rank'],
            'nope': cfg[p + 'qk_nope_head_dim'],
            'rope': cfg[p + 'qk_rope_head_dim'], 'v': cfg[p + 'v_head_dim'],
            'theta': cfg[p + 'rope_theta']}


# ---------------------------------------------------------------------------
# attention

def _query_block(T: int) -> int:
    return math.gcd(T, QUERY_BLOCK)


def allowed_keys(cfg, kind: str, w, u, c_q, positions, act=None):
    """``[T, T]`` bool: the keys each query attends to. A ``full``
    block's index scores ``I`` (float32; ``low`` rounds them) and their
    top-k are worked a query block at a time: ``[T, T]`` float32 would
    not fit at 32k positions."""
    r, mm, low = hooks(act)
    T = u.shape[0]
    causal = positions[None, :] <= positions[:, None]
    if kind == 'sliding' or act == 'no-index':
        return causal          # a window's mask is ``key_spans``'
    j, di = cfg['index_n_heads'], cfg['index_head_dim']
    n, theta = rope_columns(cfg), cfg['rope_theta']
    qi = mm(c_q, w['w_qi']).reshape(T, j, di)
    qi = r(jnp.concatenate([rope(qi[..., :n], positions, theta),
                            qi[..., n:]], -1))
    ki = r(layer_norm(mm(u, w['w_ki']), w['ki_norm_w'], w['ki_norm_b'],
                      cfg['rms_norm_eps']))
    ki = r(jnp.concatenate([rope(ki[:, :n], positions, theta), ki[:, n:]],
                           -1))
    wi = matmul(u, w['w_w'])
    k, qb = min(cfg['index_topk'], T), _query_block(T)

    def block(args):
        qs, ws, pos = args
        s = jnp.einsum('qjd,kd->qjk', qs, ki, precision=HIGHEST)
        scores = low(jnp.einsum('qjk,qj->qk', low(jax.nn.relu(s)), ws,
                                precision=HIGHEST))
        scores = jnp.where(positions[None, :] <= pos[:, None], scores, _NEG)
        top, idx = jax.lax.top_k(scores, k)
        return jnp.zeros((qb, T), bool).at[
            jnp.arange(qb)[:, None], idx].set(top > _NEG)
    return jax.lax.map(block, (
        qi.reshape(T // qb, qb, j, di), wi.reshape(T // qb, qb, j),
        positions.reshape(T // qb, qb))).reshape(T, T)


def key_spans(cfg, kind: str, T: int, act=None):
    """The keys each query block is worked against: (first ``[blocks]``,
    span, ok). Every block takes all ``T`` keys (first 0, ok ``None``:
    ``allowed_keys`` says which it attends to) but a ``sliding`` block's
    under its window: the keys outside every window of a query block
    would all be masked, and at 32k positions they are nearly all of
    them, so a block takes only the whole query blocks that its windows
    reach back into, its own included, and ok ``[blocks, QB, span]`` is
    the window's mask over those."""
    qb = _query_block(T)
    blocks = T // qb
    if kind != 'sliding' or act == 'no-window':
        return jnp.zeros((blocks,), jnp.int32), T, None
    window = cfg['sliding_window_size']
    span = min(T, qb + -(-(window - 1) // qb) * qb)
    first = jnp.maximum(jnp.arange(blocks) * qb + qb - span, 0)
    keys = first[:, None, None] + jnp.arange(span)[None, None, :]
    queries = jnp.arange(T).reshape(blocks, qb, 1)
    return first, span, (keys <= queries) & (keys > queries - window)


def attention(cfg, kind: str, w, x, act: Optional[str] = None):
    """A block's attention half (before the residual); x ``[T, d]``."""
    r, mm, low = hooks(act)
    s = attn_sizes(cfg, kind)
    T, H = x.shape[0], s['heads']
    positions = jnp.arange(T)
    a_q, a_kv = rescale(cfg, s['q_rank'], s['kv_rank'], act)
    u = r(rms_norm(x, w['norm'], cfg['rms_norm_eps']))
    c_q = r(a_q * rms_norm(mm(u, w['w_dq']), w['q_norm'],
                           cfg['rms_norm_eps']))
    raw = mm(u, w['w_dkv'])
    c_kv = r(a_kv * rms_norm(raw[:, :s['kv_rank']], w['kv_norm'],
                             cfg['rms_norm_eps']))
    k_rope = r(rope(raw[:, s['kv_rank']:], positions, s['theta']))
    scale = (s['nope'] + s['rope']) ** -0.5
    qb = _query_block(T)
    first, span, ok = key_spans(cfg, kind, T, act)
    if ok is None:
        ok = allowed_keys(cfg, kind, w, u, c_q, positions, act
                          ).reshape(T // qb, qb, T)

    def group(args):
        uq, uk, uv = args    # [rq, G, nope+rope], [G, nope, r], [G, r, v]
        q = r(jnp.einsum('tr,rgn->tgn', c_q, uq, precision=HIGHEST))
        q_rope = r(rope(q[..., s['nope']:], positions, s['theta']))
        k_nope = r(jnp.einsum('tr,gnr->tgn', c_kv, uk, precision=HIGHEST))
        v = r(jnp.einsum('tr,grv->tgv', c_kv, uv, precision=HIGHEST))

        def block(args):
            # [QB, G, nope], [QB, G, rope], [QB, span], the span's start
            qn, qr, ok, lo = args
            kn, kr, vs = k_nope, k_rope, v
            if span < T:
                kn, kr, vs = (jax.lax.dynamic_slice_in_dim(t, lo, span)
                              for t in (k_nope, k_rope, v))
            sc = (jnp.einsum('qgn,kgn->gqk', qn, kn, precision=HIGHEST)
                  + jnp.einsum('qgn,kn->gqk', qr, kr,
                               precision=HIGHEST)) * scale
            p = low(jax.nn.softmax(jnp.where(ok[None], low(sc), _NEG), -1))
            return jnp.einsum('gqk,kgv->qgv', p, vs, precision=HIGHEST)
        G = uk.shape[0]
        return jax.lax.map(block, (
            q[..., :s['nope']].reshape(T // qb, qb, G, s['nope']),
            q_rope.reshape(T // qb, qb, G, s['rope']),
            ok, first)).reshape(T, G, s['v'])
    G = min(HEAD_GROUP, H)
    uq = w['w_uq'].reshape(s['q_rank'], H // G, G, s['nope'] + s['rope'])
    o = jax.lax.map(group, (
        jnp.moveaxis(uq, 1, 0),
        w['w_uk'].reshape(H // G, G, *w['w_uk'].shape[1:]),
        w['w_uv'].reshape(H // G, G, *w['w_uv'].shape[1:])))
    o = jnp.moveaxis(o, 0, 1).reshape(T, H, s['v'])
    o = r(o * head_gate(w, u, act)[:, :, None])
    return mm(o.reshape(T, H * s['v']), w['w_o'])


# ---------------------------------------------------------------------------
# the second half of a block

def gated(h, gate, up, down, act=None):
    r, mm, _ = hooks(act)
    fed = int8_rows if act == 'bf16-w8a8' else (lambda t: t)
    inner = jax.nn.silu(matmul(fed(h), gate)) * matmul(fed(h), up)
    return mm(r(inner), down)


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
    first = cfg.get('expert_offset', 0)

    def one(out, expert):
        e, gate, up, down = expert
        share = jnp.sum(jnp.where(idx == first + e, weights, 0.0), -1)
        return out + share[:, None] * gated(h, gate.T, up.T, down, act), None
    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        jnp.arange(w['w_up'].shape[0]), w['w_gate'], w['w_up'], w['w_down']))
    return out


def shared_part(w, h, act: Optional[str] = None):
    return gated(h, w['shared_gate'], w['shared_up'], w['shared_down'], act)


def ffn(cfg, w, h, act: Optional[str] = None):
    if 'router' not in w:
        return gated(h, w['w_gate'], w['w_up'], w['w_down'], act)
    idx, weights = route(cfg, w, h, act)
    return experts_part(cfg, w, h, idx, weights, act) + shared_part(w, h, act)


def layer_forward(cfg: Dict[str, Any], kind: str, w: Dict[str, Any],
                  x: jnp.ndarray, act: Optional[str] = None) -> jnp.ndarray:
    """One block of attention ``kind`` on one sequence. x ``[seq,
    hidden]`` float32; ``w``:
    ``{'attn': leaves, 'ffn': leaves}`` as float32 (already
    int8-rounded where ``weights_int8(act)``). Any length: attention
    works in query blocks of the largest power of two up to
    ``QUERY_BLOCK`` that divides it."""
    r = hooks(act)[0]
    x = r(x + attention(cfg, kind, w['attn'], x, act))
    h = r(rms_norm(x, w['ffn']['norm'], cfg['rms_norm_eps']))
    return r(x + r(ffn(cfg, w['ffn'], h, act)))


def router_margin(cfg, w, x) -> jnp.ndarray:
    """Per token of an expert block (``x``: the stream BEFORE the
    block's second half): how far the router's choice is from a tie, in
    standard deviations of what ONE rounding of its input to bfloat16
    moves it (``reference/nemotron_h.router_margin``'s reckoning)."""
    k = cfg['num_experts_per_tok']
    h = rms_norm(x, w['norm'], cfg['rms_norm_eps'])
    s = jax.nn.sigmoid(matmul(h, w['router']))
    top, idx = jax.lax.top_k(s + w['router_bias'][None], k + 1)
    slope = s * (1.0 - s)

    def pull(i):
        at = idx[:, i]
        return (jnp.take_along_axis(slope, at[:, None], 1)
                * w['router'][:, at].T)
    moved = h * (pull(k - 1) - pull(k))
    sigma = jnp.sqrt(jnp.sum(moved * moved, -1)) * 2.0 ** -9 / 3.0 ** 0.5
    return (top[:, k - 1] - top[:, k]) / jnp.maximum(sigma, 1e-30)


def block_margin(cfg, kind: str, w, x) -> jnp.ndarray:
    """``router_margin`` of a block given the stream at its INPUT
    (``inf`` for the dense block, which routes nothing)."""
    if 'router' not in w['ffn']:
        return jnp.full((x.shape[0],), jnp.inf, F32)
    return router_margin(cfg, w['ffn'],
                         x + attention(cfg, kind, w['attn'], x))


def layer_and_margin(cfg: Dict[str, Any], kind: str, w: Dict[str, Any],
                     x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(``layer_forward`` of the reference itself, ``block_margin``) of
    one block, its attention half worked once for both: at 32k
    positions that half is most of a block's time."""
    x = x + attention(cfg, kind, w['attn'], x)
    margin = (router_margin(cfg, w['ffn'], x) if 'router' in w['ffn']
              else jnp.full((x.shape[0],), jnp.inf, F32))
    h = rms_norm(x, w['ffn']['norm'], cfg['rms_norm_eps'])
    return x + ffn(cfg, w['ffn'], h), margin


def embed(table, tokens):
    return table[tokens]


def head(cfg, final_norm, lm_head, x, act: Optional[str] = None):
    """Float32 logits of the rows given, over the vocabulary slice."""
    r, _, _ = hooks(act)
    fed = int8_rows if act == 'bf16-w8a8' else (lambda t: t)
    h = r(rms_norm(x, final_norm, cfg['rms_norm_eps']))
    return matmul(fed(h), lm_head)


def forward(cfg: Dict[str, Any], weights: Dict[str, Any], tokens,
            act: Optional[str] = None) -> jnp.ndarray:
    """Whole forward pass of one sequence, for tests at small sizes.
    ``weights``: ``{'embed', 'layers': [(kind, leaves)],
    'final_norm', 'lm_head'}``, float32. Returns logits ``[seq,
    vocab]``."""
    x = embed(weights['embed'], tokens)
    for kind, w in weights['layers']:
        if weights_int8(act):
            w = quantize_weights(w)
        x = layer_forward(cfg, kind, w, x, act)
    return head(cfg, weights['final_norm'], weights['lm_head'], x, act)
