"""Nemotron-H family (``model_type: nemotron_h``): a hybrid decoder of
Mamba-2, mixture-of-experts and attention blocks, pure JAX.

Every block is ONE mixer behind one RMSNorm and a residual,
``x = x + mixer(rmsnorm(x))``, and ``pattern`` says which mixer each
block has: ``M`` Mamba-2, ``E`` mixture of experts, ``*`` attention.
This file is the model's half of the serving interface
(``models/interface.py``): the configuration with its per-kind cache
spec, the parameter tree grouped by layer kind, its init, and the mixer
bodies as pure functions of (activations, the layer's weights, the
layer's state); the Mamba-2 mixer's body is ``models/mamba_mixer.py``,
shared with the other family that has one. ``infer/model.py`` walks
the pattern and owns what touches the caches (the page pool of the
``*`` layers, the per-slot recurrent state of the ``M`` layers). There
is no training half yet.

Parameters (``Params``): ``embed [vocab, d]``, ``final_norm [d]``,
``lm_head [d, vocab]`` and ``layers``, a dict by kind of LISTS of
per-layer dicts in pattern order (``layers['M'][i]`` is the i-th ``M``
block). Layers are separate arrays, never a stacked ``[L, ...]`` one:
a heterogeneous stack is walked by a Python loop, and a static slice of
a stacked weight would be a copy of it in every step.

- ``M``: ``norm [d]``, ``w_in [d, 2*d_inner + 2*G*N + H]`` (z | xBC |
  dt, no bias), ``conv_w [k, conv_dim]``, ``conv_b [conv_dim]``,
  ``dt_bias [H]``, ``a_log [H]``, ``d_skip [H]``, ``gate_norm
  [d_inner]``, ``w_out [d_inner, d]``.
- ``*``: ``norm``, ``wq [d, hq*hd]``, ``wk`` / ``wv [d, hkv*hd]``,
  ``wo [hq*hd, d]``; no bias and NO positional embedding (the family's
  attention applies none: the state-space layers carry position).
- ``E``: ``norm``, ``router [d, n_experts]`` and ``router_bias
  [n_experts]`` (float32), ``w_up`` and ``w_down``, BOTH stored
  ``[held, f, d]`` (the up projection transposed, so that the minor
  axis of both is ``d``: a minor axis of 1856 is not lane-aligned and
  the TPU would relay the whole stack in every call), ``shared_up [d,
  fs]``, ``shared_down [fs, d]``. Non-gated: ``relu(h @ U)**2 @ D``.

Expert parallelism is a property of the configuration, not a sharding:
``experts_held`` says how many of the router's ``n_routed_experts`` this
chip holds, from ``expert_offset`` on. The router scores and normalises
over all of them; the layer computes its own experts' part.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from skypilot_tpu.models import interface
from skypilot_tpu.models import mamba_mixer
from skypilot_tpu.ops import moe_dropless
from skypilot_tpu.ops import norms

Params = Dict[str, Any]
KINDS = ('M', 'E', '*')


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131_072
    dim: int = 2688
    pattern: str = 'MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME'
    # '*' attention
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    # 'M' Mamba-2
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # 'E' experts
    n_routed_experts: int = 128      # the router's width, as published
    experts_per_token: int = 6
    moe_ffn_dim: int = 1856
    shared_ffn_dim: int = 3712
    routed_scale: float = 2.5
    # This chip's share (expert parallelism): experts
    # [expert_offset, expert_offset + experts_held). None = all.
    experts_held: Optional[int] = None
    expert_offset: int = 0
    max_seq_len: int = 262_144
    norm_eps: float = 1e-5
    dtype: str = 'bfloat16'

    def __post_init__(self) -> None:
        bad = set(self.pattern) - set(KINDS)
        if bad or not self.pattern:
            raise ValueError(f'pattern {self.pattern!r}: kinds are {KINDS}')
        if self.mamba_heads % self.n_groups or self.n_heads % self.n_kv_heads:
            raise ValueError('heads must divide into their groups')
        held = self.held
        if (held < self.experts_per_token and 'E' in self.pattern) or \
                self.expert_offset + held > self.n_routed_experts:
            raise ValueError(
                f'experts held {self.expert_offset}+{held} of '
                f'{self.n_routed_experts}, top-{self.experts_per_token}')

    # ---- derived sizes ---------------------------------------------------
    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def held(self) -> int:
        return (self.n_routed_experts if self.experts_held is None
                else self.experts_held)

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state

    def layers(self) -> List[Tuple[str, int]]:
        """``(kind, index within its kind)`` of every block, in order."""
        seen = {k: 0 for k in KINDS}
        out = []
        for kind in self.pattern:
            out.append((kind, seen[kind]))
            seen[kind] += 1
        return out

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)

    # ---- the serving half of the model interface --------------------------
    def cache_spec(self) -> interface.CacheSpec:
        return interface.CacheSpec(
            kv_layers=self.count('*'), n_kv_heads=self.n_kv_heads,
            head_dim=self.head_dim,
            state=interface.StateSpec(
                layers=self.count('M'),
                ssm_shape=(self.mamba_heads, self.mamba_head_dim,
                           self.ssm_state),
                conv_shape=(self.conv_kernel - 1, self.conv_dim),
                conv_dtype=self.dtype))

    def paged_steps(self):
        """The step programs over this heterogeneous stack. They live
        in ``infer/model.py`` beside the dense block's, which imports
        this module for the mixer bodies: hence the late import."""
        from skypilot_tpu.infer import model
        return model.hybrid_steps(self)

    def init_params(self, key) -> 'Params':
        return init_params(self, key)

    def embed(self, params: 'Params', tokens):
        return params['embed'][tokens]

    def head(self, params: 'Params', x):
        return head(self, params, x)

    def serving_refusals(self) -> Dict[str, str]:
        """Engine switches this model cannot run with, each with the
        reason (``interface.check_engine`` raises them)."""
        state = ('a slot holds recurrent (Mamba-2) state, which '
                 'summarises every token it has seen: ')
        return {
            'prefix_cache': state + 'a prefill that starts past offset 0 '
            'would need a snapshot of the state at the matched prefix, '
            'and pages hold none',
            'spec_k': state + 'rejected draft tokens cannot be rolled '
            'back out of it as page rows are',
            'fused_prefill': 'the fused mixed step is not built over a '
            'heterogeneous stack',
            'kv_int8': 'the int8 page flavor was never run at this '
            "model's 2 KV heads x group 16",
            'tp': 'the recurrent state and the expert layer have no '
            'partition rules yet',
            'quantize': 'int8 weights are not built for the expert '
            'stacks or the Mamba-2 projections',
            'dense': 'the recurrent state lives beside the PAGED pool '
            'only (paged=True)',
            'kv_wire': state + 'the wire format carries K/V pages only, '
            'so an imported prefix would have no state behind it',
        }

    @classmethod
    def tiny(cls, **kw) -> 'NemotronHConfig':
        """CPU-test preset: one period and a little more of the
        pattern, every mechanism present, widths shrunk."""
        base = dict(
            vocab_size=512, dim=64, pattern='MEM*EME', n_heads=4,
            n_kv_heads=2, head_dim=16, mamba_heads=4, mamba_head_dim=8,
            ssm_state=16, n_groups=2, conv_kernel=4, chunk_size=16,
            n_routed_experts=8, experts_per_token=2, moe_ffn_dim=32,
            shared_ffn_dim=48, max_seq_len=256)
        base.update(kw)
        return cls(**base)

    @classmethod
    def nano_30b_a3b_ep2(cls, **kw) -> 'NemotronHConfig':
        """Nemotron-3-Nano-30B-A3B at its published widths as ONE of
        two chips that share each layer by expert parallelism: the
        pattern's first 16 blocks, 64 of the 128 routed experts, half
        of the vocabulary (benchmark/configs/
        nemotron-3-nano-30b-a3b.serve-bf16-ep2.json)."""
        base = dict(vocab_size=65_536, pattern='MEMEM*EMEMEM*EME',
                    experts_held=64, max_seq_len=4096)
        base.update(kw)
        return cls(**base)


# ---------------------------------------------------------------------------
# init

def _dt_bias(key, config: NemotronHConfig) -> jnp.ndarray:
    """Inverse softplus of a step drawn log-uniform in
    [time_step_min, time_step_max], floored (the Mamba-2 recipe)."""
    u = jax.random.uniform(key, (config.mamba_heads,), jnp.float32)
    lo, hi = jnp.log(config.time_step_min), jnp.log(config.time_step_max)
    dt = jnp.maximum(jnp.exp(lo + u * (hi - lo)), config.time_step_floor)
    return dt + jnp.log(-jnp.expm1(-dt))


def init_layer(config: NemotronHConfig, kind: str, key) -> Dict[str, Any]:
    d, dt = config.dim, jnp.dtype(config.dtype)
    std, out_std = d ** -0.5, d ** -0.5 / (2 * config.n_layers) ** 0.5
    k = jax.random.split(key, 8)

    def normal(key, shape, s, dtype=dt):
        return (jax.random.normal(key, shape, jnp.float32) * s).astype(dtype)
    norm = jnp.ones((d,), dt)
    if kind == 'M':
        h, di = config.mamba_heads, config.d_inner
        return {
            'norm': norm,
            'w_in': normal(k[0], (d, 2 * di + 2 * config.n_groups
                                  * config.ssm_state + h), std),
            'conv_w': normal(k[1], (config.conv_kernel, config.conv_dim),
                             config.conv_kernel ** -0.5, jnp.float32),
            'conv_b': normal(k[2], (config.conv_dim,), 0.1, jnp.float32),
            'dt_bias': _dt_bias(k[3], config),
            'a_log': jnp.log(jax.random.uniform(
                k[4], (h,), jnp.float32, 1.0, 16.0)),
            'd_skip': jnp.ones((h,), jnp.float32),
            'gate_norm': jnp.ones((di,), dt),
            'w_out': normal(k[5], (di, d), out_std * (d / di) ** 0.5)}
    if kind == '*':
        q = config.n_heads * config.head_dim
        kv = config.n_kv_heads * config.head_dim
        return {'norm': norm, 'wq': normal(k[0], (d, q), std),
                'wk': normal(k[1], (d, kv), std),
                'wv': normal(k[2], (d, kv), std),
                'wo': normal(k[3], (q, d), out_std * (d / q) ** 0.5)}
    f, fs, e = config.moe_ffn_dim, config.shared_ffn_dim, config.held
    return {'norm': norm,
            'router': normal(k[0], (d, config.n_routed_experts), std,
                             jnp.float32),
            'router_bias': normal(k[1], (config.n_routed_experts,), 0.1,
                                  jnp.float32),
            'w_up': normal(k[2], (e, f, d), std),
            'w_down': normal(k[3], (e, f, d), out_std * (d / f) ** 0.5),
            'shared_up': normal(k[4], (d, fs), std),
            'shared_down': normal(k[5], (fs, d),
                                  out_std * (d / fs) ** 0.5)}


def init_params(config: NemotronHConfig, key) -> Params:
    dt = jnp.dtype(config.dtype)
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    layers: Dict[str, List[Any]] = {k: [] for k in KINDS}
    for i, (kind, _) in enumerate(config.layers()):
        layers[kind].append(init_layer(config, kind,
                                       jax.random.fold_in(k_layers, i)))
    return {
        'embed': (jax.random.normal(k_embed, (config.vocab_size, config.dim),
                                    jnp.float32)).astype(dt),
        'layers': layers,
        'final_norm': jnp.ones((config.dim,), dt),
        'lm_head': (jax.random.normal(k_head, (config.dim, config.vocab_size),
                                      jnp.float32)
                    * config.dim ** -0.5).astype(dt)}


# ---------------------------------------------------------------------------
# mixer bodies: pure functions of (activations, weights, state)

def mamba_chunk(config: NemotronHConfig, layer, x, ssm, conv, true_len):
    """The ``M`` block's mixer over one prompt chunk of ONE sequence:
    its norm, then ``mamba_mixer.chunk`` (x ``[C, d]``: the residual
    stream; the state's contract is there)."""
    h = norms.rms_norm(x, layer['norm'], config.norm_eps)
    return mamba_mixer.chunk(config, layer, h, ssm, conv, true_len)


def mamba_decode(config: NemotronHConfig, layer, x, ssm, conv, active):
    """The ``M`` block's mixer for one token of every slot: its norm,
    then ``mamba_mixer.decode`` (x ``[slots, d]``)."""
    h = norms.rms_norm(x, layer['norm'], config.norm_eps)
    return mamba_mixer.decode(config, layer, h, ssm, conv, active)


def attn_qkv(config: NemotronHConfig, layer, x):
    """Norm and the three projections of a ``*`` block; x ``[T, d]``.
    No positional embedding. Returns q ``[T, hkv, group, hd]``, k and v
    ``[T, hkv, hd]``."""
    T = x.shape[0]
    hq, hkv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    h = norms.rms_norm(x, layer['norm'], config.norm_eps)
    q = jnp.dot(h, layer['wq']).reshape(T, hkv, hq // hkv, hd)
    k = jnp.dot(h, layer['wk']).reshape(T, hkv, hd)
    v = jnp.dot(h, layer['wv']).reshape(T, hkv, hd)
    return q, k, v


def moe_mixer(config: NemotronHConfig, layer, x, valid):
    """The ``E`` mixer; x ``[T, d]``, valid ``[T]`` bool (padded or
    inactive rows are routed nowhere and touch no expert). Returns
    (output ``[T, d]``, ``moe_dropless.STATS`` counts int32)."""
    h = norms.rms_norm(x, layer['norm'], config.norm_eps)
    with jax.named_scope('moe.route'):
        idx, w = moe_dropless.route(
            h, layer['router'], layer['router_bias'],
            config.experts_per_token, config.routed_scale)
    with jax.named_scope('moe.experts'):
        routed, stats = moe_dropless.local_experts(
            h, idx, w, layer['w_up'], layer['w_down'], valid,
            config.expert_offset)
    with jax.named_scope('moe.shared'):
        up = jnp.dot(h, layer['shared_up'],
                     preferred_element_type=jnp.float32)
        act = jnp.square(jax.nn.relu(up)).astype(h.dtype)
        shared = jnp.dot(act, layer['shared_down'],
                         preferred_element_type=jnp.float32)
    return (routed + shared).astype(x.dtype), stats


def head(config: NemotronHConfig, params: Params, x):
    """Final norm and the untied head; float32 logits."""
    x = norms.rms_norm(x, params['final_norm'], config.norm_eps)
    return jnp.dot(x, params['lm_head'],
                   preferred_element_type=jnp.float32)
