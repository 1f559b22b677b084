"""The serving half of the model interface (ROADMAP D6).

What ``infer/engine.py`` and ``infer/model.py`` ask of a model's
configuration in place of ``llama.LlamaConfig``:

- ``max_seq_len``, ``vocab_size``: plain attributes;
- ``cache_spec()``: what kinds of per-request state the model's layers
  keep, kind by kind (``CacheSpec``): how many layers write K/V pages
  and their head geometry, how many keep recurrent per-slot state and
  its shapes, and how many keep LATENT rows (one row a token in place
  of K and V per head), growing with the context or bounded by a
  window. The engine builds the cache from it and nothing else;
- ``serving_refusals()``: engine switches the model cannot run with,
  each with its reason (a model with none need not define it);
- ``paged_steps()``: its step programs (``infer/model.PagedSteps``),
  reached through ``infer/model.paged_steps(config)``;
- ``init_params(key)``: random parameters of its family.

One mechanism for all four: a method on the configuration, and the
dense block (``llama.LlamaConfig``, which defines none) as the default.
This module names no model.

The training half (loss, partition specs, the pipeline's stage body)
is not here yet: no training cell exists to guard it.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class StateSpec:
    """Recurrent state a slot keeps, per layer that has it."""
    layers: int
    ssm_shape: Tuple[int, ...]      # float32, e.g. (heads, head_dim, N)
    conv_shape: Tuple[int, ...]     # (kernel - 1, conv_dim)
    conv_dtype: str = 'bfloat16'


@dataclasses.dataclass(frozen=True)
class LatentSpec:
    """Latent-attention layers: ONE row a token and layer, shared by
    every head. ``full`` layers keep every row of the context (and,
    where ``index_row`` is not 0, an indexer key beside it, in a pool
    of its own); ``window`` layers attend to the last ``window``
    positions, the query's own included, and keep no row behind that:
    their pool is bounded by the window and a prefill chunk a slot,
    whatever the context."""
    full_layers: int
    full_row: int                   # values a row: latent | rope key
    index_row: int                  # the indexer's key (0: none)
    window_layers: int = 0
    window_row: int = 0
    window: int = 0


@dataclasses.dataclass(frozen=True)
class CacheSpec:
    kv_layers: int                  # layers that write K/V pages
    n_kv_heads: int
    head_dim: int
    state: Optional[StateSpec] = None
    latent: Optional[LatentSpec] = None


def cache_spec(config: Any) -> CacheSpec:
    """``config.cache_spec()``, or the dense block's: every layer
    writes K/V and none keeps state."""
    if hasattr(config, 'cache_spec'):
        return config.cache_spec()
    return CacheSpec(kv_layers=config.n_layers,
                     n_kv_heads=config.n_kv_heads, head_dim=config.head_dim)


def refusals(config: Any) -> Dict[str, str]:
    """``config.serving_refusals()``: switch -> reason; none for a model
    that does not define it."""
    return (config.serving_refusals()
            if hasattr(config, 'serving_refusals') else {})


def check_engine(config: Any, ecfg: Any) -> None:
    """Raise ``ValueError`` for the first engine switch that ``config``
    refuses, naming the switch and the reason."""
    refused = refusals(config)
    asked = (
        ('dense', not ecfg.paged, 'paged=False'),
        ('prefix_cache', ecfg.prefix_cache, 'prefix_cache=True'),
        ('spec_k', ecfg.spec_k > 0, f'spec_k={ecfg.spec_k}'),
        ('fused_prefill', ecfg.fused_prefill, 'fused_prefill=True'),
        ('kv_int8', ecfg.kv_dtype == 'int8', "kv_dtype='int8'"),
        ('tp', ecfg.tp > 1, f'tp={ecfg.tp}'),
        ('quantize', ecfg.quantize, 'quantize=True'),
    )
    for switch, on, shown in asked:
        if on and switch in refused:
            raise ValueError(
                f'{type(config).__name__} cannot be served with '
                f'{shown}: {refused[switch]}')


def init_params(config: Any, key: Any) -> Any:
    """``config.init_params(key)``, or the dense block's."""
    if hasattr(config, 'init_params'):
        return config.init_params(key)
    from skypilot_tpu.models import llama
    return llama.init_params(config, key)
