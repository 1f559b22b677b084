"""Recurrent per-slot state beside the paged KV pool.

A model with state-space layers keeps, for every slot and every such
layer, a fixed-size state: the SSM state (float32) and the last
``kernel - 1`` inputs of the layer's causal convolution. It does not
grow with the sequence, so it is not paged: a slot IS the unit of
allocation, and the engine's slot table is its allocator. The page pool
beside it (``paged_cache.PagedKVCache``) holds only the model's
attention layers (its ``n_layers`` is their count, not the depth).

``HybridCache`` is the one object the step programs carry: the pool and
the state both ride it as donated, in-place-updated arrays. The state
is a TUPLE of per-layer arrays, not one ``[L, ...]`` array: the stack
is walked by a Python loop, and a layer's array is rewritten in the
rows of the slots that decode (``ops/mamba2.ssd_decode_live``: a slot
that is not active is not read either) or in one slot's row (prefill),
aliased onto itself.

The rule that keeps a slot's state right: **a prefill that starts at
offset 0 starts from a zero state** (``slot_state``). A fresh request,
a preempted one that is prefilled again and a resumed one all start
there, so freeing a slot needs no device work beyond its length, and
whatever a finished or inactive slot's state was is never read.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from skypilot_tpu.infer import paged_cache as paged_cache_lib
from skypilot_tpu.models import interface


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class HybridCache:
    kv: paged_cache_lib.PagedKVCache
    ssm: Tuple[jnp.ndarray, ...]    # per state layer [slots, *ssm_shape] f32
    conv: Tuple[jnp.ndarray, ...]   # per state layer [slots, k-1, conv_dim]

    @property
    def lengths(self) -> jnp.ndarray:
        return self.kv.lengths

    @property
    def page_bytes(self) -> int:
        return self.kv.page_bytes

    @property
    def state_bytes(self) -> int:
        """HBM bytes of the recurrent state, all slots."""
        return sum(a.nbytes for a in self.ssm + self.conv)


def init_hybrid_cache(spec: interface.CacheSpec, n_slots: int,
                      n_pages: int, page_size: int,
                      dtype=jnp.bfloat16) -> HybridCache:
    st = spec.state
    return HybridCache(
        kv=paged_cache_lib.init_paged_cache(
            spec.kv_layers, n_slots, n_pages, page_size, spec.n_kv_heads,
            spec.head_dim, dtype=dtype),
        ssm=tuple(jnp.zeros((n_slots, *st.ssm_shape), jnp.float32)
                  for _ in range(st.layers)),
        conv=tuple(jnp.zeros((n_slots, *st.conv_shape),
                             jnp.dtype(st.conv_dtype))
                   for _ in range(st.layers)))


def slot_state(cache: HybridCache, layer: int, slot, offset):
    """(ssm, conv) of ``slot`` in state layer ``layer`` as a prefill
    chunk at ``offset`` must see it: zero when the prefill starts."""
    ssm = jax.lax.dynamic_index_in_dim(cache.ssm[layer], slot, 0, False)
    conv = jax.lax.dynamic_index_in_dim(cache.conv[layer], slot, 0, False)
    fresh = offset == 0
    return (jnp.where(fresh, 0.0, ssm),
            jnp.where(fresh, jnp.zeros((), conv.dtype), conv))


def _with(arrs: Tuple[jnp.ndarray, ...], layer: int,
          arr: jnp.ndarray) -> Tuple[jnp.ndarray, ...]:
    return arrs[:layer] + (arr.astype(arrs[layer].dtype),) + arrs[layer + 1:]


def with_slot_state(cache: HybridCache, layer: int, slot, ssm,
                    conv) -> HybridCache:
    """The cache with one slot's row of one layer's state rewritten."""
    def put(arrs, new):
        return _with(arrs, layer, jax.lax.dynamic_update_index_in_dim(
            arrs[layer], new.astype(arrs[layer].dtype), slot, 0))
    return dataclasses.replace(cache, ssm=put(cache.ssm, ssm),
                               conv=put(cache.conv, conv))


def with_layer_state(cache: HybridCache, layer: int, ssm,
                     conv) -> HybridCache:
    """The cache with one layer's arrays replaced (a decode step's:
    the same buffers, advanced in place)."""
    return dataclasses.replace(cache, ssm=_with(cache.ssm, layer, ssm),
                               conv=_with(cache.conv, layer, conv))


def free_slot(cache: HybridCache, slot) -> HybridCache:
    """Device half of freeing a slot: its length goes to 0. The state
    is left as it is (see the module docstring)."""
    return dataclasses.replace(
        cache, kv=paged_cache_lib.free_slot(cache.kv, slot))
