"""Falcon-H1 family (``model_type: falcon_h1``): a dense decoder whose
every block runs attention AND a Mamba-2 mixer side by side, pure JAX.

A block, ``x`` ``[T, d]``, no bias anywhere but the convolution's::

    h = rmsnorm(x; norm)
    a = attn(h * attention_in) * attention_out      rope on q and k,
                                                    k scaled by key
    s = ssm(h * ssm_in) * ssm_out                   models/mamba_mixer.py,
                                                    W_in's output scaled
    x = x + a + s
    x = x + (silu(g W_gate * mlp[0]) * (g W_up)) W_down * mlp[1],
        g = rmsnorm(x; ff_norm)

and around the stack ``E[token] * embedding`` and ``rmsnorm(x) W_head *
lm_head``. The eleven multipliers are the published maximal-update
ones: part of the model, not of its weights.

This file is the model's half of the serving interface
(``models/interface.py``), as ``models/nemotron_h.py`` is its family's:
the configuration with its cache spec (EVERY layer keeps both K/V pages
and recurrent state), the parameter tree, its init, and the block's
parts as pure functions of (activations, weights, state).
``infer/model.py``'s hybrid step programs walk the stack (every block
is of the one kind ``P``) and own what touches the caches. There is no
training half.

Parameters (``Params``): ``embed [vocab, d]``, ``final_norm [d]``,
``lm_head [d, vocab]`` and ``layers = {'P': [block, ...]}``, separate
arrays a block (see ``nemotron_h``: a stacked weight sliced in a Python
loop is copied every step). A block: ``norm``, ``wq [d, hq*hd]``, ``wk``
/ ``wv [d, hkv*hd]``, ``wo [hq*hd, d]``, the mixer's leaves as
``mamba_mixer`` names them, ``ff_norm``, ``w_gate`` / ``w_up [d, f]``,
``w_down [f, d]``.

Multipliers are applied to the float32 result of the product they
follow, before it is rounded to the activation type.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from skypilot_tpu.models import interface
from skypilot_tpu.models import mamba_mixer
from skypilot_tpu.ops import norms
from skypilot_tpu.ops import rope as rope_lib

Params = Dict[str, Any]
KIND = 'P'          # the one kind of block: attention beside the mixer
F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    """Falcon-H1-34B-Instruct as published."""
    vocab_size: int = 261_120
    dim: int = 5120
    n_layers: int = 72
    # attention
    n_heads: int = 20
    n_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e11
    # the Mamba-2 mixer (d_inner = heads x head width = mamba_d_ssm)
    mamba_heads: int = 32
    mamba_head_dim: int = 128
    ssm_state: int = 256
    n_groups: int = 2
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # the gated MLP
    ffn_dim: int = 21_504
    # maximal-update multipliers
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    # over W_in's output: z | x | B | C | dt
    ssm_multipliers: Tuple[float, ...] = (
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
        0.3535533905932738)
    mlp_multipliers: Tuple[float, float] = (
        0.1767766952966369, 0.011160714285714284)
    max_seq_len: int = 262_144
    norm_eps: float = 1e-5
    dtype: str = 'bfloat16'

    def __post_init__(self) -> None:
        if self.mamba_heads % self.n_groups or self.n_heads % self.n_kv_heads:
            raise ValueError('heads must divide into their groups')
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError('ssm_multipliers has five parts (z, x, B, C, '
                             'dt), mlp_multipliers two (gate, down)')

    # ---- derived sizes ---------------------------------------------------
    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state

    @property
    def in_proj(self) -> int:
        return self.d_inner + self.conv_dim + self.mamba_heads

    def layers(self) -> List[Tuple[str, int]]:
        """``(kind, index within its kind)`` of every block, in order:
        what ``infer/model.py``'s hybrid programs walk."""
        return [(KIND, i) for i in range(self.n_layers)]

    def count(self, kind: str) -> int:
        return self.n_layers if kind == KIND else 0

    def in_mult(self) -> np.ndarray:
        """``ssm_multipliers`` spread over ``W_in``'s output columns
        (a constant of the program, float32)."""
        gn = self.n_groups * self.ssm_state
        widths = (self.d_inner, self.d_inner, gn, gn, self.mamba_heads)
        return np.repeat(np.asarray(self.ssm_multipliers, np.float32),
                         widths)

    # ---- the serving half of the model interface --------------------------
    def cache_spec(self) -> interface.CacheSpec:
        return interface.CacheSpec(
            kv_layers=self.n_layers, n_kv_heads=self.n_kv_heads,
            head_dim=self.head_dim,
            state=interface.StateSpec(
                layers=self.n_layers,
                ssm_shape=(self.mamba_heads, self.mamba_head_dim,
                           self.ssm_state),
                conv_shape=(self.conv_kernel - 1, self.conv_dim),
                conv_dtype=self.dtype))

    def paged_steps(self):
        from skypilot_tpu.infer import model
        return model.hybrid_steps(self)

    def init_params(self, key) -> 'Params':
        return init_params(self, key)

    def embed(self, params: Params, tokens):
        return embed(self, params, tokens)

    def head(self, params: Params, x):
        return head(self, params, x)

    def serving_refusals(self) -> Dict[str, str]:
        """Engine switches this model cannot run with, each with the
        reason (``interface.check_engine`` raises them)."""
        state = ('a slot holds recurrent (Mamba-2) state in every '
                 'block, which summarises every token it has seen: ')
        return {
            'prefix_cache': state + 'a prefill that starts past offset 0 '
            'would need a snapshot of the state at the matched prefix, '
            'and pages hold none',
            'spec_k': state + 'rejected draft tokens cannot be rolled '
            'back out of it as page rows are',
            'fused_prefill': 'the fused mixed step is not built over a '
            'block that advances recurrent state',
            'kv_int8': 'the int8 page flavor was never run at this '
            "model's 4 KV heads x group 5",
            'tp': 'the recurrent state has no partition rules yet',
            'quantize': 'int8 weights are not built for the Mamba-2 '
            'projections',
            'dense': 'the recurrent state lives beside the PAGED pool '
            'only (paged=True)',
            'kv_wire': state + 'the wire format carries K/V pages only, '
            'so an imported prefix would have no state behind it',
        }

    @classmethod
    def tiny(cls, **kw) -> 'FalconH1Config':
        """CPU-test preset: the group of 5 query heads a KV head kept,
        2 SSM groups, a state wider than the mixer's head, the
        published multipliers, widths shrunk."""
        base = dict(
            vocab_size=512, dim=64, n_layers=3, n_heads=10, n_kv_heads=2,
            head_dim=16, mamba_heads=4, mamba_head_dim=8, ssm_state=16,
            n_groups=2, chunk_size=16, ffn_dim=96, max_seq_len=256)
        base.update(kw)
        return cls(**base)

    @classmethod
    def h1_34b_pp8(cls, **kw) -> 'FalconH1Config':
        """Falcon-H1-34B-Instruct at its published widths as ONE of
        the 8 stages of a pipeline: 9 whole blocks of 72 and, the
        embedding and the head being vocabulary-parallel over the same
        8 chips, 1/8 of the vocabulary (benchmark/configs/
        falcon-h1-34b.serve-bf16-pp8.json)."""
        base = dict(vocab_size=32_640, n_layers=9, max_seq_len=4096)
        base.update(kw)
        return cls(**base)


# ---------------------------------------------------------------------------
# init

def init_layer(config: FalconH1Config, key) -> Dict[str, Any]:
    d, dt = config.dim, jnp.dtype(config.dtype)
    std, out_std = d ** -0.5, d ** -0.5 / (2 * config.n_layers) ** 0.5
    k = jax.random.split(key, 12)

    def normal(key, shape, s, dtype=dt):
        return (jax.random.normal(key, shape, F32) * s).astype(dtype)
    h, di, f = config.mamba_heads, config.d_inner, config.ffn_dim
    q = config.n_heads * config.head_dim
    kv = config.n_kv_heads * config.head_dim
    u = jax.random.uniform(k[7], (h,), F32)
    lo, hi = jnp.log(config.time_step_min), jnp.log(config.time_step_max)
    step = jnp.maximum(jnp.exp(lo + u * (hi - lo)), config.time_step_floor)
    return {
        'norm': jnp.ones((d,), dt),
        'wq': normal(k[0], (d, q), std), 'wk': normal(k[1], (d, kv), std),
        'wv': normal(k[2], (d, kv), std),
        'wo': normal(k[3], (q, d), out_std * (d / q) ** 0.5),
        'w_in': normal(k[4], (d, config.in_proj), std),
        'conv_w': normal(k[5], (config.conv_kernel, config.conv_dim),
                         config.conv_kernel ** -0.5, F32),
        'conv_b': normal(k[6], (config.conv_dim,), 0.1, F32),
        'dt_bias': step + jnp.log(-jnp.expm1(-step)),
        'a_log': jnp.log(jax.random.uniform(k[8], (h,), F32, 1.0, 16.0)),
        'd_skip': jnp.ones((h,), F32),
        'gate_norm': jnp.ones((di,), dt),
        'w_out': normal(k[9], (di, d), out_std * (d / di) ** 0.5),
        'ff_norm': jnp.ones((d,), dt),
        'w_gate': normal(k[10], (d, f), std),
        'w_up': normal(k[11], (d, f), std),
        'w_down': normal(jax.random.fold_in(k[11], 1), (f, d),
                         out_std * (d / f) ** 0.5)}


def init_params(config: FalconH1Config, key) -> Params:
    dt = jnp.dtype(config.dtype)
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    return {
        'embed': jax.random.normal(k_embed, (config.vocab_size, config.dim),
                                   F32).astype(dt),
        'layers': {KIND: [init_layer(config, jax.random.fold_in(k_layers, i))
                          for i in range(config.n_layers)]},
        'final_norm': jnp.ones((config.dim,), dt),
        'lm_head': (jax.random.normal(k_head, (config.dim, config.vocab_size),
                                      F32) * config.dim ** -0.5).astype(dt)}


# ---------------------------------------------------------------------------
# the block's parts: pure functions of (activations, weights, state)

def _scaled(h, mult: float):
    return h if mult == 1.0 else h * jnp.asarray(mult, h.dtype)


def _dot(x, w, mult: float = 1.0):
    """``x @ w`` in float32, times a multiplier."""
    y = jnp.dot(x, w, preferred_element_type=F32)
    return y if mult == 1.0 else y * mult


def embed(config: FalconH1Config, params: Params, tokens):
    x = params['embed'][tokens]
    return (x.astype(F32) * config.embedding_multiplier).astype(x.dtype)


def rope_at(config: FalconH1Config, positions):
    """(cos, sin) at ``positions [T]``, once a step for every block."""
    return rope_lib.rope_at(config.head_dim, config.rope_theta, positions)


def block_norm(config: FalconH1Config, layer, x):
    """The ONE norm both mixers of a block read."""
    return norms.rms_norm(x, layer['norm'], config.norm_eps)


def attn_qkv(config: FalconH1Config, layer, h, rope):
    """The three projections of the block's normed input ``h [T, d]``,
    the key's multiplier, and rope (half-split pairs, all of the head)
    on q and k; rope: ``rope_at`` of the rows' positions. Returns q
    ``[T, hkv, group, hd]``, k and v ``[T, hkv, hd]``."""
    T, dt = h.shape[0], h.dtype
    hq, hkv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    h = _scaled(h, config.attention_in_multiplier)
    q = _dot(h, layer['wq']).astype(dt).reshape(T, hq, hd)
    k = _dot(h, layer['wk'], config.key_multiplier).astype(dt)
    k = k.reshape(T, hkv, hd)
    v = jnp.dot(h, layer['wv']).reshape(T, hkv, hd)
    cos, sin = rope
    q = rope_lib.apply_rope(q, cos, sin)
    k = rope_lib.apply_rope(k, cos, sin)
    return q.reshape(T, hkv, hq // hkv, hd), k, v


def attn_out(config: FalconH1Config, layer, att):
    """The attention branch's term of the residual, float32; att
    ``[T, hq*hd]``."""
    return _dot(att, layer['wo'], config.attention_out_multiplier)


def _ssm_out(config, y):
    return y.astype(F32) * config.ssm_out_multiplier


def ssm_chunk(config: FalconH1Config, layer, h, ssm, conv, true_len):
    """The mixer branch over one prompt chunk (``mamba_mixer.chunk``'s
    contract); returns its term of the residual in float32."""
    y, ssm, conv = mamba_mixer.chunk(
        config, layer, _scaled(h, config.ssm_in_multiplier), ssm, conv,
        true_len, config.in_mult())
    return _ssm_out(config, y), ssm, conv


def ssm_decode(config: FalconH1Config, layer, h, ssm, conv, active):
    """The mixer branch for one token of every slot
    (``mamba_mixer.decode``'s contract)."""
    y, ssm, conv = mamba_mixer.decode(
        config, layer, _scaled(h, config.ssm_in_multiplier), ssm, conv,
        active, config.in_mult())
    return _ssm_out(config, y), ssm, conv


def mixed(x, a, s):
    """``x + a + s``: the residual with both branches' float32 terms,
    rounded once."""
    return (x.astype(F32) + a + s).astype(x.dtype)


def mlp(config: FalconH1Config, layer, x):
    """``x`` plus the gated MLP behind its own norm."""
    g = norms.rms_norm(x, layer['ff_norm'], config.norm_eps)
    gate = _dot(g, layer['w_gate'], config.mlp_multipliers[0])
    act = (jax.nn.silu(gate) * _dot(g, layer['w_up'])).astype(x.dtype)
    down = _dot(act, layer['w_down'], config.mlp_multipliers[1])
    return (x.astype(F32) + down).astype(x.dtype)


def head(config: FalconH1Config, params: Params, x):
    """Final norm and the untied head; float32 logits."""
    x = norms.rms_norm(x, params['final_norm'], config.norm_eps)
    return _dot(x, params['lm_head'], config.lm_head_multiplier)
