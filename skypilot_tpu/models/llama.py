"""Llama-family decoder-only transformer, pure JAX, scan-over-layers.

The framework's flagship model (BASELINE.md: Llama-3-8B finetune is the
north-star workload). Design choices for TPU/XLA:

- **Params are a pytree of stacked arrays** ([n_layers, ...] leading axis)
  consumed by ``lax.scan`` — one layer gets compiled once, not n_layers
  times, and remat applies per scan step.
- **bf16 params/activations, fp32 softmax/norm internals** — MXU-native.
- GQA (n_kv_heads < n_heads), SwiGLU MLP, RMSNorm, RoPE — Llama-3
  architecture.
- Attention dispatches to the Pallas flash kernel on TPU
  (``ops/attention.py``) and dense elsewhere.

Sharding of these params is defined in ``parallel/sharding.py`` (the model
is sharding-agnostic; `jit` + NamedSharding do the work).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from skypilot_tpu.ops import attention as attention_lib
from skypilot_tpu.ops import norms
from skypilot_tpu.ops import quant as quant_lib
from skypilot_tpu.ops import rope as rope_lib

Params = Dict[str, Any]

# Tree skeleton of one stacked layer group (leaves are placeholders) —
# lets sharding/pipeline code tree_map PartitionSpecs over the layer dict
# without materializing params.
LLAMA_LAYER_TREE: Dict[str, int] = {
    'attn_norm': 0, 'wq': 0, 'wk': 0, 'wv': 0, 'wo': 0,
    'mlp_norm': 0, 'w_gate': 0, 'w_up': 0, 'w_down': 0,
}


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128_256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14_336
    max_seq_len: int = 8192
    rope_theta: float = 500_000.0
    norm_eps: float = 1e-5
    dtype: str = 'bfloat16'
    attention_impl: str = 'auto'    # 'auto' | 'flash' | 'dense'
    # Flash-attention tile sizes (None → ops/attention defaults). Tuned
    # per chip generation.
    attn_block_q: Optional[int] = None
    attn_block_k: Optional[int] = None
    remat: bool = True              # rematerialize each layer in backward
    # 'full' (default): recompute everything — minimum memory, and what
    # every pre-existing config was sized against. 'dots' saves matmul
    # outputs and recomputes only elementwise ops (measured worse on the
    # v5e bench: too much saved, HBM pressure). 'save_attn' saves ONLY
    # the attention outputs — the flash kernel is the priciest recompute
    # while its output is a tiny [b, s, d]; +1.5% tok/s at seq 8192,
    # noise-level at 2048.
    remat_policy: str = 'full'      # 'full' | 'dots' | 'save_attn'
    # Vocab-chunked cross-entropy (ops/cross_entropy.py). None = dense
    # (XLA's fused log-softmax wins at 32k vocab — measured on v5e);
    # set for 100k+ vocabs where fp32 [b*s, V] logits (4.3 GB for
    # Llama-3's 128256 at b4 s2048) must never materialize.
    loss_vocab_chunks: Optional[int] = None
    # Fused Pallas cross-entropy (ops/cross_entropy.py
    # fused_cross_entropy): logits tiles live and die in VMEM — HBM
    # traffic drops to the matmul operands. Requires b*s and vocab
    # divisible by 512. Overrides loss_vocab_chunks when set.
    fused_loss: bool = False

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def num_params(self) -> int:
        d, f, v = self.dim, self.ffn_dim, self.vocab_size
        per_layer = (d * self.n_heads * self.head_dim            # wq
                     + 2 * d * self.n_kv_heads * self.head_dim   # wk, wv
                     + self.n_heads * self.head_dim * d          # wo
                     + 3 * d * f                                 # gate/up/down
                     + 2 * d)                                    # norms
        return self.n_layers * per_layer + 2 * v * d + d

    # ---- presets --------------------------------------------------------
    @staticmethod
    def llama3_8b(**kw) -> 'LlamaConfig':
        kw.setdefault('loss_vocab_chunks', 16)   # 128k vocab
        return LlamaConfig(**kw)

    @staticmethod
    def llama3_70b(**kw) -> 'LlamaConfig':
        kw.setdefault('loss_vocab_chunks', 16)   # 128k vocab
        return LlamaConfig(dim=8192, n_layers=80, n_heads=64, n_kv_heads=8,
                           ffn_dim=28_672, **kw)

    @staticmethod
    def bench_350m(**kw) -> 'LlamaConfig':
        """~350M params: fits one v5e chip with Adam states for bench."""
        base = dict(vocab_size=32_768, dim=1024, n_layers=16,
                    n_heads=16, n_kv_heads=8, ffn_dim=4096,
                    max_seq_len=2048)
        base.update(kw)
        return LlamaConfig(**base)

    @staticmethod
    def bench_1b(**kw) -> 'LlamaConfig':
        """~1B params: the single-chip bench workload. Fills the v5e MXU
        far better than the 350M config (dim 1536 keeps matmuls wide
        enough); full remat + bf16 Adam moments fit it in 16 GiB HBM
        with seq 2048. Flash tiles 512x512: the round-3 on-chip sweep
        measured 0.578 MFU vs 0.520 at the generic 256x256 (bigger
        tiles amortize the VMEM pipeline; 1024 tiles regress — VMEM
        pressure), and seq-8192 batch-1 trains at 0.617 MFU without
        OOM (the backward kernel's O(s) memory claim, proven)."""
        base = dict(vocab_size=32_768, dim=1536, n_layers=24,
                    n_heads=12, n_kv_heads=12, ffn_dim=6144,
                    max_seq_len=2048, remat_policy='full',
                    attn_block_q=512, attn_block_k=512)
        base.update(kw)
        return LlamaConfig(**base)

    @staticmethod
    def tiny(**kw) -> 'LlamaConfig':
        """Test-sized config (CPU-fast)."""
        base = dict(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                    n_kv_heads=2, ffn_dim=128, max_seq_len=128,
                    dtype='float32')
        base.update(kw)
        return LlamaConfig(**base)


# Checkpoint tag shared by attention_block's checkpoint_name and the
# 'save_attn' policy — save_only_these_names silently matches nothing if
# the strings drift, which would degrade to full remat with no error.
_ATTN_OUT_NAME = 'attn_out'


def init_params(config: LlamaConfig, key: jax.Array) -> Params:
    """Scaled-normal init, layers stacked on axis 0."""
    dtype = jnp.dtype(config.dtype)
    d, hd = config.dim, config.head_dim
    L = config.n_layers
    k_embed, k_layers, k_head = jax.random.split(key, 3)

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(
            dtype)

    ks = jax.random.split(k_layers, 7)
    scale = d ** -0.5
    out_scale = scale / (2 * L) ** 0.5   # GPT-2-style residual scaling
    layers = {
        'attn_norm': jnp.ones((L, d), dtype),
        'wq': normal(ks[0], (L, d, config.n_heads * hd), scale),
        'wk': normal(ks[1], (L, d, config.n_kv_heads * hd), scale),
        'wv': normal(ks[2], (L, d, config.n_kv_heads * hd), scale),
        'wo': normal(ks[3], (L, config.n_heads * hd, d), out_scale),
        'mlp_norm': jnp.ones((L, d), dtype),
        'w_gate': normal(ks[4], (L, d, config.ffn_dim), scale),
        'w_up': normal(ks[5], (L, d, config.ffn_dim), scale),
        'w_down': normal(ks[6], (L, config.ffn_dim, d), out_scale),
    }
    return {
        'embed': normal(k_embed, (config.vocab_size, d), 1.0),
        'layers': layers,
        'final_norm': jnp.ones((d,), dtype),
        'lm_head': normal(k_head, (d, config.vocab_size), scale),
    }


def attention_block(config: LlamaConfig, x: jnp.ndarray, layer: Params,
                    cos: jnp.ndarray, sin: jnp.ndarray,
                    positions: Optional[jnp.ndarray]
                    ) -> tuple:
    """norm → QKV → RoPE → attention → residual. THE shared attention
    block — MoE layers and the inference prefill path reuse it so the
    attention math exists exactly once. Returns (x, k, v) with k/v
    post-RoPE [b, s, kv_heads, head_dim] (cache insertion needs them)."""
    b, s, d = x.shape
    hq, hkv, hd = config.n_heads, config.n_kv_heads, config.head_dim

    h = norms.rms_norm(x, layer['attn_norm'], config.norm_eps)
    # qdot: plain `@` for training params, dequantizing matmul for the
    # int8 serving path (ops/quant.py) — one attention implementation.
    q = quant_lib.qdot(h, layer['wq']).reshape(b, s, hq, hd)
    k = quant_lib.qdot(h, layer['wk']).reshape(b, s, hkv, hd)
    v = quant_lib.qdot(h, layer['wv']).reshape(b, s, hkv, hd)
    q = rope_lib.apply_rope(q, cos, sin, positions)
    k = rope_lib.apply_rope(k, cos, sin, positions)
    # [b, s, h, hd] -> [b, h, s, hd] for the attention kernels.
    att = attention_lib.attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True,
        impl=config.attention_impl,
        block_q=config.attn_block_q, block_k=config.attn_block_k)
    # Named for selective remat ('save_attn' policy): saving just this
    # tensor (b*s*d, tiny vs the O(s^2)-work flash kernel that produced
    # it) lets the backward skip re-running attention entirely.
    att = jax.ad_checkpoint.checkpoint_name(att, _ATTN_OUT_NAME)
    att = att.transpose(0, 2, 1, 3).reshape(b, s, hq * hd)
    return x + quant_lib.qdot(att, layer['wo']), k, v


def mlp_block(config: LlamaConfig, x: jnp.ndarray,
              layer: Params) -> jnp.ndarray:
    """norm -> SwiGLU -> residual; shared with the inference paths so
    the MLP math (and its quantized form) exists exactly once."""
    h = norms.rms_norm(x, layer['mlp_norm'], config.norm_eps)
    gate = jax.nn.silu(quant_lib.qdot(h, layer['w_gate']))
    return x + quant_lib.qdot(gate * quant_lib.qdot(h, layer['w_up']),
                              layer['w_down'])


def _layer(config: LlamaConfig, x: jnp.ndarray, layer: Params,
           cos: jnp.ndarray, sin: jnp.ndarray,
           positions: Optional[jnp.ndarray]) -> jnp.ndarray:
    x, _, _ = attention_block(config, x, layer, cos, sin, positions)
    return mlp_block(config, x, layer)


def backbone(config: LlamaConfig, params: Params, tokens: jnp.ndarray,
             positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """tokens [b, s] int32 -> final-norm hidden states [b, s, d]."""
    x = params['embed'][tokens]
    cos, sin = rope_lib.rope_frequencies(config.head_dim,
                                         config.max_seq_len,
                                         config.rope_theta)

    def body(carry, layer):
        fn = _layer
        if config.remat:
            if config.remat_policy == 'dots':
                policy = (jax.checkpoint_policies
                          .dots_with_no_batch_dims_saveable)
            elif config.remat_policy == 'save_attn':
                # Full remat EXCEPT the attention outputs: the flash
                # kernel is the most expensive recompute per layer while
                # its output is only [b, s, d] — the best FLOPs-per-byte
                # trade on the menu.
                policy = jax.checkpoint_policies.save_only_these_names(
                    _ATTN_OUT_NAME)
            elif config.remat_policy == 'full':
                policy = None
            else:
                # A typo must not silently bench as full remat.
                raise ValueError(
                    f'Unknown remat_policy {config.remat_policy!r}; '
                    f"expected 'full', 'dots' or 'save_attn'")
            fn = jax.checkpoint(_layer, static_argnums=(0,),
                                policy=policy)
        return fn(config, carry, layer, cos, sin, positions), None

    x, _ = jax.lax.scan(body, x, params['layers'])
    return norms.rms_norm(x, params['final_norm'], config.norm_eps)


def forward(config: LlamaConfig, params: Params, tokens: jnp.ndarray,
            positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """tokens [b, s] int32 -> logits [b, s, vocab] (fp32)."""
    x = backbone(config, params, tokens, positions)
    return quant_lib.qdot(x, params['lm_head']).astype(jnp.float32)


def loss_fn(config: LlamaConfig, params: Params, tokens: jnp.ndarray,
            targets: jnp.ndarray,
            mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Causal LM cross-entropy.

    Dense fp32 log-softmax by default (XLA fuses it well at 32k vocab);
    ``config.loss_vocab_chunks`` switches to the vocab-chunked
    custom-VJP path (ops/cross_entropy.py) that never materializes the
    fp32 [b*s, vocab] logits — required headroom at 100k+ vocabs.
    """
    if config.fused_loss:
        from skypilot_tpu.ops import cross_entropy as ce
        b, s = tokens.shape
        x = backbone(config, params, tokens)
        nll = ce.fused_cross_entropy(
            x.reshape(b * s, config.dim), params['lm_head'],
            targets.reshape(b * s).astype(jnp.int32)).reshape(b, s)
    elif config.loss_vocab_chunks:
        from skypilot_tpu.ops import cross_entropy as ce
        b, s = tokens.shape
        x = backbone(config, params, tokens)
        nll = ce.chunked_cross_entropy(
            x.reshape(b * s, config.dim), params['lm_head'],
            targets.reshape(b * s).astype(jnp.int32),
            config.loss_vocab_chunks).reshape(b, s)
    else:
        logits = forward(config, params, tokens)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None],
                                   axis=-1)[..., 0]
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
    return jnp.mean(nll)


def flops_per_token(config: LlamaConfig) -> float:
    """Training FLOPs/token ~ 6 * params + attention quadratic term
    (2*2*3*s*d per token at seq s, fwd+bwd)."""
    base = 6.0 * config.num_params
    attn = 12.0 * config.n_layers * config.max_seq_len * config.head_dim \
        * config.n_heads
    return base + attn
