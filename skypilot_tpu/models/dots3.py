"""dots3-note family (``model_type: dots3_note``): latent attention of
two kinds in one decoder, a learned sparse selection over the full
kind, and a gated expert layer. Pure JAX.

Every block is pre-norm with a plain residual around each half, ``x =
x + attn(rmsnorm(x)); x = x + ffn(rmsnorm(x))``. ``layer_types`` says
which attention a block has:

- ``full``: multi-head LATENT attention (MLA) with a sparse-attention
  indexer. A token leaves ONE row in the cache, ``[c_kv (kv_lora_rank)
  | k_rope (qk_rope_dim)]``, shared by every head, and one indexer key
  beside it. A query scores every cached indexer key (``I[t, s] =
  sum_j w[t, j] relu(qI[t, j] . kI[s])``), keeps the ``index_topk``
  positions of largest score (all of them while there are fewer), and
  attends to those rows only.
- ``sliding``: latent attention of its own sizes (``swa_*``) over the
  last ``window`` positions, the query's own included; no indexer.

Two forms of the same attention, chosen by the step program
(``infer/latent_steps.py``). ABSORBED: ``W_uk`` is folded into the
query (``q_abs = q_nope @ W_uk[h]``) and ``W_uv`` into the output, so
that attention contracts over the cached row itself and no key or
value is up-projected per head: a decode step's one query a slot, and
the window layers. UP-PROJECTED (``expand_rows``): every head given its
own keys and values from the cached rows once, for a prefill chunk of a
``full`` block, whose many queries share them. Both kinds gate each
head's output by a sigmoid of a linear map of the block's input
(``head_gate``) before ``W_o``.

Three readings of the published configuration are isolated in one
function each, so that a correction is one line (the benchmark's
configuration file lists them under ``assumed``):
``latent_rescale`` (``apply_mla_qkv_lora_rescale``), ``head_gate``
(``attention_gate_type: headwise``) and ``rope_split`` (which columns
of the indexer's vectors are rotated).

Block ``i < first_k_dense`` has a dense SwiGLU MLP; every other block
an expert layer: ``ops/moe_dropless.route`` (sigmoid scores, a
correction bias for the choice, weights normalised over the chosen),
gated experts ``(silu(x G) * (x U)) D``, and one shared expert.
Expert parallelism is a property of the configuration, as in
``models/nemotron_h.py``: ``experts_held`` of the router's
``n_routed_experts`` live here, from ``expert_offset`` on.

Parameters: ``embed [vocab, d]``, ``final_norm [d]``, ``lm_head [d,
vocab]`` and ``layers``, a LIST of per-block dicts ``{'attn': {...},
'ffn': {...}}`` (separate arrays, walked by a Python loop).

- ``attn``: ``norm [d]``, ``w_dq [d, rq]``, ``q_norm [rq]``, ``w_uq
  [rq, H * (nope + rope)]``, ``w_dkv [d, rkv + rope]``, ``kv_norm
  [rkv]``, ``w_uk [H, nope, rkv]`` and ``w_uv [H, rkv, v]`` (the two
  halves of the published ``kv_b_proj``, laid out for the absorbed
  form), ``w_gate [d, H]``, ``w_o [H * v, d]``; a ``full`` block adds
  the indexer's ``w_qi [rq, J * di]``, ``w_ki [d, di]``, ``ki_norm_w``
  / ``ki_norm_b [di]`` (a LayerNorm) and ``w_w [d, J]``.
- ``ffn`` dense: ``norm``, ``w_gate`` / ``w_up [d, f]``, ``w_down [f,
  d]``. Experts: ``norm``, ``router [d, E]`` and ``router_bias [E]``
  (float32), ``w_gate`` / ``w_up`` / ``w_down [held, f, d]`` (the minor
  axis is the model width, as ``ops/moe_dropless`` wants),
  ``shared_gate`` / ``shared_up [d, fs]``, ``shared_down [fs, d]``.

This file is the model's half of the serving interface
(``models/interface.py``). ``infer/latent_steps.py`` walks the blocks
and owns what touches the cache (``infer/latent_cache.py``). There is
no training half.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from skypilot_tpu.models import interface
from skypilot_tpu.ops import latent_attention
from skypilot_tpu.ops import moe_dropless
from skypilot_tpu.ops import norms
from skypilot_tpu.ops import rope as rope_lib

Params = Dict[str, Any]
KINDS = ('full', 'sliding')
_PUBLISHED_TYPES = (('full',) + ('full', 'sliding', 'sliding', 'sliding') * 11
                    + ('full',))


@dataclasses.dataclass(frozen=True)
class Dots3Config:
    vocab_size: int = 152_064
    dim: int = 5120
    layer_types: Tuple[str, ...] = _PUBLISHED_TYPES
    first_k_dense: int = 1
    dense_ffn_dim: int = 13_824
    # 'full' blocks: MLA and the indexer
    n_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_dim: int = 128
    rope_theta: float = 8e7
    index_heads: int = 64
    index_dim: int = 128
    index_topk: int = 2048
    # 'sliding' blocks
    swa_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_dim: int = 192
    swa_qk_rope_dim: int = 64
    swa_v_dim: int = 128
    swa_rope_theta: float = 5e4
    window: int = 513               # keys a query sees, itself included
    lora_rescale: bool = True       # apply_mla_qkv_lora_rescale
    # experts
    n_routed_experts: int = 256     # the router's width, as published
    experts_per_token: int = 8
    moe_ffn_dim: int = 1536
    shared_ffn_dim: int = 1536
    routed_scale: float = 1.0
    # This chip's share (expert parallelism): experts
    # [expert_offset, expert_offset + experts_held). None = all.
    experts_held: Optional[int] = None
    expert_offset: int = 0
    max_seq_len: int = 524_288
    norm_eps: float = 1e-5
    dtype: str = 'bfloat16'

    def __post_init__(self) -> None:
        bad = set(self.layer_types) - set(KINDS)
        if bad or not self.layer_types:
            raise ValueError(f'layer_types {self.layer_types!r}: kinds are '
                             f'{KINDS}')
        held = self.held
        if (held < self.experts_per_token
                and self.n_layers > self.first_k_dense) or \
                self.expert_offset + held > self.n_routed_experts:
            raise ValueError(
                f'experts held {self.expert_offset}+{held} of '
                f'{self.n_routed_experts}, top-{self.experts_per_token}')

    # ---- derived sizes ---------------------------------------------------
    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def held(self) -> int:
        return (self.n_routed_experts if self.experts_held is None
                else self.experts_held)

    def count(self, kind: str) -> int:
        return self.layer_types.count(kind)

    def kind_index(self, block: int) -> int:
        """Block ``block``'s index among the blocks of its kind: its
        layer in that kind's page pool."""
        return self.layer_types[:block].count(self.layer_types[block])

    def attn_sizes(self, kind: str) -> 'AttnSizes':
        if kind == 'full':
            return AttnSizes(self.n_heads, self.q_lora_rank,
                             self.kv_lora_rank, self.qk_nope_dim,
                             self.qk_rope_dim, self.v_dim, self.rope_theta)
        return AttnSizes(self.swa_heads, self.swa_q_lora_rank,
                         self.swa_kv_lora_rank, self.swa_qk_nope_dim,
                         self.swa_qk_rope_dim, self.swa_v_dim,
                         self.swa_rope_theta)

    # ---- the serving half of the model interface --------------------------
    def cache_spec(self) -> interface.CacheSpec:
        full, win = self.attn_sizes('full'), self.attn_sizes('sliding')
        return interface.CacheSpec(
            kv_layers=0, n_kv_heads=0, head_dim=0,
            latent=interface.LatentSpec(
                full_layers=self.count('full'), full_row=full.row,
                index_row=self.index_dim,
                window_layers=self.count('sliding'), window_row=win.row,
                window=self.window))

    def paged_steps(self):
        from skypilot_tpu.infer import latent_steps
        return latent_steps.steps()

    def init_params(self, key) -> 'Params':
        return init_params(self, key)

    def serving_refusals(self) -> Dict[str, str]:
        """Engine switches this model cannot run with, each with the
        reason (``interface.check_engine`` raises them)."""
        window = ('a window layer keeps no row behind its window, and '
                  'a full layer one latent row and one indexer key a '
                  'token in pools of their own: ')
        return {
            'prefix_cache': window + 'the radix tree shares K/V pages of '
            'ONE pool; a matched prefix would need its latent and '
            'indexer pages shared and the window layers rebuilt from '
            'rows that were freed',
            'spec_k': 'no verify program is built over latent pages '
            '(the selection would run once a draft position)',
            'fused_prefill': 'the fused mixed step is not built over a '
            'heterogeneous stack',
            'kv_int8': 'latent rows are not quantised: the int8 page '
            'flavour scales a row per KV head, and a latent row has none',
            'tp': 'the latent pools and the expert layer have no '
            'partition rules yet',
            'quantize': 'int8 weights are not built for the expert '
            'stacks or the latent projections',
            'dense': 'latent rows live in PAGED pools only (paged=True)',
            'kv_wire': window + 'the wire format carries K/V pages only',
        }

    @classmethod
    def tiny(cls, **kw) -> 'Dots3Config':
        """CPU-test preset: the dense block and one period, every
        mechanism present, widths shrunk; top-k and window both small
        enough to lie under a test's context."""
        base = dict(
            vocab_size=512, dim=64,
            layer_types=('full', 'full', 'sliding', 'sliding', 'sliding'),
            dense_ffn_dim=96, n_heads=4, q_lora_rank=32, kv_lora_rank=16,
            qk_nope_dim=16, qk_rope_dim=8, v_dim=16, index_heads=4,
            index_dim=16, index_topk=8, swa_heads=2, swa_q_lora_rank=32,
            swa_kv_lora_rank=32, swa_qk_nope_dim=24, swa_qk_rope_dim=8,
            swa_v_dim=16, window=5, n_routed_experts=8,
            experts_per_token=2, moe_ffn_dim=32, shared_ffn_dim=32,
            max_seq_len=256)
        base.update(kw)
        return cls(**base)

    @classmethod
    def note_prev_ep8(cls, **kw) -> 'Dots3Config':
        """dots3-note-prev at its published widths as ONE of eight
        chips that share each layer by expert parallelism with
        data-parallel attention: blocks 0-4 (the dense block and one
        whole period), 32 of the 256 routed experts, an eighth of the
        vocabulary (benchmark/configs/
        dots3-note-prev.serve-bf16-ep8.json)."""
        base = dict(vocab_size=19_008, layer_types=_PUBLISHED_TYPES[:5],
                    experts_held=32, max_seq_len=33_792)
        base.update(kw)
        return cls(**base)


@dataclasses.dataclass(frozen=True)
class AttnSizes:
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    theta: float

    @property
    def row(self) -> int:
        """Values a token leaves in the cache: latent | rope key."""
        return self.kv_rank + self.rope

    @property
    def scale(self) -> float:
        return (self.nope + self.rope) ** -0.5


# ---------------------------------------------------------------------------
# the three readings (module docstring), one function each

def latent_rescale(config: Dots3Config, sizes: AttnSizes
                   ) -> Tuple[float, float]:
    """``apply_mla_qkv_lora_rescale``: (a_q, a_kv), what the normed
    latents are multiplied by: ``sqrt(hidden / rank)`` each."""
    if not config.lora_rescale:
        return 1.0, 1.0
    return ((config.dim / sizes.q_rank) ** 0.5,
            (config.dim / sizes.kv_rank) ** 0.5)


def head_gate(layer: Dict[str, Any], u: jnp.ndarray) -> jnp.ndarray:
    """``attention_gate_type: headwise``: one scalar a head, a sigmoid
    of a linear map of the block's normed input; ``[T, H]`` float32."""
    return jax.nn.sigmoid(jnp.dot(u, layer['w_gate'],
                                  preferred_element_type=jnp.float32))


def rope_split(index_dim: int, rope_dim: int) -> int:
    """The indexer rotates the FIRST ``rope_dim`` of its ``index_dim``
    columns and leaves the rest."""
    return min(rope_dim, index_dim)


# ---------------------------------------------------------------------------
# init

def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init_attn(config: Dots3Config, kind: str, key) -> Dict[str, Any]:
    s, d, dt = config.attn_sizes(kind), config.dim, jnp.dtype(config.dtype)
    k = jax.random.split(key, 12)
    out_std = (s.heads * s.v) ** -0.5 / (2 * config.n_layers) ** 0.5
    layer = {
        'norm': jnp.ones((d,), dt),
        'w_dq': _normal(k[0], (d, s.q_rank), d ** -0.5, dt),
        'q_norm': jnp.ones((s.q_rank,), dt),
        'w_uq': _normal(k[1], (s.q_rank, s.heads * (s.nope + s.rope)),
                        config.dim ** -0.5, dt),
        'w_dkv': _normal(k[2], (d, s.row), d ** -0.5, dt),
        'kv_norm': jnp.ones((s.kv_rank,), dt),
        'w_uk': _normal(k[3], (s.heads, s.nope, s.kv_rank),
                        config.dim ** -0.5, dt),
        'w_uv': _normal(k[4], (s.heads, s.kv_rank, s.v),
                        config.dim ** -0.5, dt),
        'w_gate': _normal(k[5], (d, s.heads), d ** -0.5, dt),
        'w_o': _normal(k[6], (s.heads * s.v, d), out_std, dt)}
    if kind == 'full':
        j, di = config.index_heads, config.index_dim
        layer.update(
            w_qi=_normal(k[7], (s.q_rank, j * di), config.dim ** -0.5, dt),
            w_ki=_normal(k[8], (d, di), d ** -0.5, dt),
            ki_norm_w=jnp.ones((di,), dt), ki_norm_b=jnp.zeros((di,), dt),
            w_w=_normal(k[9], (d, j), d ** -0.5, dt))
    return layer


def init_ffn(config: Dots3Config, block: int, key) -> Dict[str, Any]:
    d, dt = config.dim, jnp.dtype(config.dtype)
    k = jax.random.split(key, 8)
    depth = (2 * config.n_layers) ** 0.5
    norm = jnp.ones((d,), dt)
    if block < config.first_k_dense:
        f = config.dense_ffn_dim
        return {'norm': norm,
                'w_gate': _normal(k[0], (d, f), d ** -0.5, dt),
                'w_up': _normal(k[1], (d, f), d ** -0.5, dt),
                'w_down': _normal(k[2], (f, d), f ** -0.5 / depth, dt)}
    f, fs, e = config.moe_ffn_dim, config.shared_ffn_dim, config.held
    return {'norm': norm,
            'router': _normal(k[0], (d, config.n_routed_experts),
                              d ** -0.5, jnp.float32),
            'router_bias': _normal(k[1], (config.n_routed_experts,), 0.1,
                                   jnp.float32),
            'w_gate': _normal(k[2], (e, f, d), d ** -0.5, dt),
            'w_up': _normal(k[3], (e, f, d), d ** -0.5, dt),
            'w_down': _normal(k[4], (e, f, d), f ** -0.5 / depth, dt),
            'shared_gate': _normal(k[5], (d, fs), d ** -0.5, dt),
            'shared_up': _normal(k[6], (d, fs), d ** -0.5, dt),
            'shared_down': _normal(k[7], (fs, d), fs ** -0.5 / depth, dt)}


def init_params(config: Dots3Config, key) -> Params:
    dt = jnp.dtype(config.dtype)
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    layers = []
    for i, kind in enumerate(config.layer_types):
        ka, kf = jax.random.split(jax.random.fold_in(k_layers, i))
        layers.append({'attn': init_attn(config, kind, ka),
                       'ffn': init_ffn(config, i, kf)})
    return {
        'embed': _normal(k_embed, (config.vocab_size, config.dim), 1.0, dt),
        'layers': layers,
        'final_norm': jnp.ones((config.dim,), dt),
        'lm_head': _normal(k_head, (config.dim, config.vocab_size),
                           config.dim ** -0.5, dt)}


# ---------------------------------------------------------------------------
# block halves: pure functions of (activations, weights)

def _rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float
          ) -> jnp.ndarray:
    """Rotate ``x [T, heads, dim]`` at ``positions [T]`` (``ops/rope``'s
    pairing: column i with column i + dim/2)."""
    dim = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    return rope_lib.apply_rope(x, jnp.cos(ang), jnp.sin(ang))


def attn_inputs(config: Dots3Config, kind: str, layer: Dict[str, Any],
                x: jnp.ndarray, positions: jnp.ndarray) -> Dict[str, Any]:
    """Everything a block's attention computes from its own tokens; x
    ``[T, d]``, positions ``[T]``. Returns ``u`` (the normed input),
    ``q`` ``[T, H, rkv + rope]`` (the absorbed query beside its rotated
    part: what meets a cached row), ``q_heads`` ``[T, H, nope + rope]``
    (the query as it is: what meets an up-projected key), ``row`` ``[T,
    rkv + rope]`` (what the token leaves in the cache) and, for a
    ``full`` block, ``qi``
    ``[T, J, di]``, ``ki`` ``[T, di]`` and ``wi`` ``[T, J]`` float32."""
    s = config.attn_sizes(kind)
    T = x.shape[0]
    a_q, a_kv = latent_rescale(config, s)
    with jax.named_scope('attn.latent'):
        u = norms.rms_norm(x, layer['norm'], config.norm_eps)
        c_q = norms.rms_norm(jnp.dot(u, layer['w_dq']), layer['q_norm'],
                             config.norm_eps)
        c_q = (c_q.astype(jnp.float32) * a_q).astype(x.dtype)
        q = jnp.dot(c_q, layer['w_uq']).reshape(T, s.heads, s.nope + s.rope)
        q_nope, q_rope = q[..., :s.nope], q[..., s.nope:]
        q_rope = _rope(q_rope, positions, s.theta)
        q_abs = jnp.einsum('thn,hnr->thr', q_nope, layer['w_uk'])
        raw = jnp.dot(u, layer['w_dkv'])
        c_kv = norms.rms_norm(raw[:, :s.kv_rank], layer['kv_norm'],
                              config.norm_eps)
        c_kv = (c_kv.astype(jnp.float32) * a_kv).astype(x.dtype)
        k_rope = _rope(raw[:, None, s.kv_rank:], positions, s.theta)[:, 0]
        out = {'u': u, 'q': jnp.concatenate([q_abs, q_rope], -1),
               'q_heads': jnp.concatenate([q_nope, q_rope], -1),
               'row': jnp.concatenate([c_kv, k_rope], -1)}
    if kind == 'full':
        with jax.named_scope('attn.index'):
            j, di = config.index_heads, config.index_dim
            r = rope_split(di, s.rope)
            qi = jnp.dot(c_q, layer['w_qi']).reshape(T, j, di)
            qi = jnp.concatenate(
                [_rope(qi[..., :r], positions, s.theta), qi[..., r:]], -1)
            ki = _layer_norm(jnp.dot(u, layer['w_ki']), layer['ki_norm_w'],
                             layer['ki_norm_b'], config.norm_eps)
            ki = jnp.concatenate(
                [_rope(ki[:, None, :r], positions, s.theta)[:, 0],
                 ki[:, r:]], -1)
            out.update(qi=qi, ki=ki, wi=jnp.dot(
                u, layer['w_w'], preferred_element_type=jnp.float32))
    return out


def _layer_norm(x, weight, bias, eps):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), -1, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def expand_rows(sizes: AttnSizes, rows: jnp.ndarray, w_uk: jnp.ndarray,
                w_uv: jnp.ndarray, width: int, v_width: int
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Cached rows ``[S, rkv + rope]`` up-projected for the heads whose
    ``w_uk [G, nope, rkv]`` / ``w_uv [G, rkv, v]`` are given: keys ``[G,
    S, width]`` (``k_nope | k_rope``, the rotated part the same for
    every head, zero-padded to ``width``) and values ``[G, S,
    v_width]`` (zero-padded likewise)."""
    c_kv, k_rope = rows[:, :sizes.kv_rank], rows[:, sizes.kv_rank:]
    G, S = w_uk.shape[0], rows.shape[0]
    k_nope = jnp.einsum('sr,gnr->gsn', c_kv, w_uk)
    keys = jnp.concatenate([
        k_nope, jnp.broadcast_to(k_rope[None], (G, S, sizes.rope)),
        jnp.zeros((G, S, width - sizes.nope - sizes.rope), rows.dtype)], -1)
    values = jnp.einsum('sr,grv->gsv', c_kv, w_uv)
    return keys, jnp.pad(values, ((0, 0), (0, 0), (0, v_width - sizes.v)))


def attn_output(config: Dots3Config, kind: str, layer: Dict[str, Any],
                u: jnp.ndarray, o_latent: Optional[jnp.ndarray] = None,
                o_heads: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """To the block's attention half ``[T, d]`` from what attention
    summed, either of the cached latents (``o_latent [T, H, rkv]``, the
    absorbed form: ``W_uv`` a head first) or of the heads' own values
    (``o_heads [T, H, v]``): the head gate, then ``W_o``."""
    T = u.shape[0]
    if o_heads is None:
        with jax.named_scope('attn.latent'):
            o_heads = latent_attention.einsum_f32(
                'thr,hrv->thv', o_latent.astype(u.dtype), layer['w_uv'])
    o = o_heads
    with jax.named_scope('attn.gate'):
        o = (o * head_gate(layer, u)[:, :, None]).astype(u.dtype)
    with jax.named_scope('attn.latent'):
        return jnp.dot(o.reshape(T, -1), layer['w_o'])


def ffn(config: Dots3Config, block: int, layer: Dict[str, Any],
        x: jnp.ndarray, valid: jnp.ndarray
        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """A block's second half; x ``[T, d]``, valid ``[T]`` bool (padded
    or inactive rows reach no expert). Returns (output ``[T, d]``,
    ``moe_dropless.STATS`` counts int32, zero for the dense block)."""
    h = norms.rms_norm(x, layer['norm'], config.norm_eps)
    if block < config.first_k_dense:
        with jax.named_scope('mlp'):
            out = _swiglu(h, layer['w_gate'], layer['w_up'], layer['w_down'])
        return out.astype(x.dtype), jnp.zeros((3,), jnp.int32)
    with jax.named_scope('moe.route'):
        idx, w = moe_dropless.route(
            h, layer['router'], layer['router_bias'],
            config.experts_per_token, config.routed_scale)
    with jax.named_scope('moe.experts'):
        routed, stats = moe_dropless.local_experts(
            h, idx, w, layer['w_up'], layer['w_down'], valid,
            config.expert_offset, w_gate=layer['w_gate'])
    with jax.named_scope('moe.shared'):
        shared = _swiglu(h, layer['shared_gate'], layer['shared_up'],
                         layer['shared_down'])
    return (routed + shared).astype(x.dtype), stats


def _swiglu(h, w_gate, w_up, w_down):
    gate = jnp.dot(h, w_gate, preferred_element_type=jnp.float32)
    up = jnp.dot(h, w_up, preferred_element_type=jnp.float32)
    return jnp.dot((jax.nn.silu(gate) * up).astype(h.dtype), w_down,
                   preferred_element_type=jnp.float32)


def head(config: Dots3Config, params: Params, x: jnp.ndarray) -> jnp.ndarray:
    """Final norm and the untied head; float32 logits."""
    x = norms.rms_norm(x, params['final_norm'], config.norm_eps)
    return jnp.dot(x, params['lm_head'], preferred_element_type=jnp.float32)
