"""Sharding rules: PartitionSpecs for model params, optimizer state, data.

Megatron-style TP composed with ZeRO-3-style FSDP, expressed as
NamedShardings (XLA inserts the all-gathers/reduce-scatters):

- attention qkv projections: column-parallel (heads over ``tp``), fsdp on
  the input dim.
- attention output / MLP down: row-parallel (``tp`` on input dim).
- MLP gate/up: column-parallel.
- embed: vocab over ``tp`` (vocab-parallel embedding), model dim over
  ``fsdp``; lm_head the transpose.
- Optimizer state inherits its parameter's sharding (ZeRO-3).
- Batch data: sharded over (``dp``, ``fsdp``) jointly — fsdp is also a data
  axis.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

LLAMA_PARAM_SPECS: Dict[str, Any] = {
    'embed': P('tp', 'fsdp'),
    'layers': {
        'attn_norm': P(None, None),
        'wq': P(None, 'fsdp', 'tp'),
        'wk': P(None, 'fsdp', 'tp'),
        'wv': P(None, 'fsdp', 'tp'),
        'wo': P(None, 'tp', 'fsdp'),
        'mlp_norm': P(None, None),
        'w_gate': P(None, 'fsdp', 'tp'),
        'w_up': P(None, 'fsdp', 'tp'),
        'w_down': P(None, 'tp', 'fsdp'),
    },
    'final_norm': P(None),
    'lm_head': P('fsdp', 'tp'),
}

BATCH_SPEC = P(('dp', 'fsdp'), None)           # [batch, seq]


def attention_spec(mesh, n_heads: int, n_kv_heads: int) -> P:
    """[batch, heads, seq, head_dim] layout for attention kernels that
    run per shard (ops/attention.flash_attention): batch over the data
    axes, heads over ``tp`` — where the column-parallel qkv projections
    already leave them — when ``tp`` divides both head counts (GQA
    groups then stay whole per shard); otherwise every tp shard
    computes all heads. Names only axes ``mesh`` has."""
    batch = tuple(a for a in ('dp', 'fsdp') if a in mesh.axis_names)
    tp = mesh.shape.get('tp', 1)
    heads = 'tp' if (tp > 1 and n_heads % tp == 0
                     and n_kv_heads % tp == 0) else None
    return P(batch or None, heads, None, None)


def param_shardings(mesh: Mesh, params: Any) -> Any:
    """NamedShardings matching the params pytree (LLAMA_PARAM_SPECS
    broadcast over identical tree structure).

    Int8-quantized trees (ops/quant.py QuantArray) are handled too:
    the ``q`` field shards like the original weight; ``scale`` drops
    the contraction axis it was reduced over (-2 for matmul weights,
    -1 for the per-row embedding table) from the weight's spec — this
    is what lets an int8 70B shard over a tp mesh."""
    specs = LLAMA_PARAM_SPECS

    def to_sharding(path, leaf):
        node = specs
        keys = [p.key if hasattr(p, 'key') else
                getattr(p, 'name', None) or p.idx for p in path]
        consumed = 0
        for key in keys:
            if isinstance(node, dict):
                node = node[key]
                consumed += 1
            else:
                break
        rest = keys[consumed:]
        if not rest:
            return NamedSharding(mesh, node)
        [field] = rest                      # QuantArray member
        if field == 'q':
            return NamedSharding(mesh, node)
        assert field == 'scale', field
        parts = list(node) + [None] * (len(leaf.shape) + 1 - len(node))
        if keys[0] == 'embed':
            spec = P(*parts[:1])            # per-row: [vocab]
        else:
            spec = P(*(parts[:-2] + parts[-1:]))   # drop the in axis
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(to_sharding, params)


def opt_state_shardings(mesh: Mesh, opt_state: Any, params: Any) -> Any:
    """Optimizer state shards like its parameter (ZeRO-3). Non-pytree-of-
    params leaves (step counters etc.) are replicated."""
    p_shard = param_shardings(mesh, params)
    flat_params, _ = jax.tree_util.tree_flatten(params)
    flat_shards, _ = jax.tree_util.tree_flatten(p_shard)
    shard_by_shape = {}
    for p, s in zip(flat_params, flat_shards):
        shard_by_shape.setdefault((p.shape, p.dtype), s)

    def to_sharding(leaf):
        key = (getattr(leaf, 'shape', ()), getattr(leaf, 'dtype', None))
        if key in shard_by_shape:
            return shard_by_shape[key]
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map(to_sharding, opt_state)


def shard_pytree(tree: Any, shardings: Any) -> Any:
    """Place a host pytree onto the mesh with the given shardings."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, s), tree, shardings)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, BATCH_SPEC)
