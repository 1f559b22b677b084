"""Operations and bytes of the Nemotron-H hybrid's decode step and of
its two new mechanisms, from shapes and counts alone.

As ``work.py`` for the dense block: the work the *algorithm* needs,
counted with the benchmark so that it reads the same whatever
implements it. Padded rows, slots that are not live and experts that no
live token reached are not counted. ``cfg`` is the configuration file's
dict (published key names; ``n_routed_experts`` is what this share
holds).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark.weights_nemotron_h import pattern, sizes
from benchmark.work import peaks, roofline_share  # noqa: F401  (re-used)


def counts(cfg: Dict[str, Any]) -> Dict[str, int]:
    kinds = pattern(cfg)
    return {k: kinds.count(k) for k in 'ME*'}


def mamba_matmul_params(cfg) -> int:
    s = sizes(cfg)
    return s['d'] * s['in_proj'] + s['d_inner'] * s['d']


def state_elements(cfg) -> int:
    """One slot's SSM state in one ``M`` block."""
    return (cfg['mamba_num_heads'] * cfg['mamba_head_dim']
            * cfg['ssm_state_size'])


def attn_matmul_params(cfg) -> int:
    s = sizes(cfg)
    return 2 * s['d'] * s['q'] + 2 * s['d'] * s['kv']


def expert_params(cfg) -> int:
    """One routed expert: up and down."""
    s = sizes(cfg)
    return 2 * s['d'] * s['f']


def moe_dense_params(cfg) -> int:
    """What every token of an ``E`` block passes: router and shared."""
    s = sizes(cfg)
    return s['d'] * cfg['n_routed_experts_published'] + 2 * s['d'] * s['fs']


def total_params(cfg) -> int:
    n, s = counts(cfg), sizes(cfg)
    k = cfg['conv_kernel']
    m = (mamba_matmul_params(cfg) + (k + 1) * s['conv_dim']
         + 3 * cfg['mamba_num_heads'] + s['d_inner'] + s['d'])
    e = (cfg['n_routed_experts'] * expert_params(cfg) + moe_dense_params(cfg)
         + cfg['n_routed_experts_published'] + s['d'])
    a = attn_matmul_params(cfg) + s['d']
    return (n['M'] * m + n['E'] * e + n['*'] * a
            + 2 * s['d'] * cfg['vocab_size'] + s['d'])


def state_bytes_per_slot(cfg, conv_itemsize: int = 2) -> int:
    s = sizes(cfg)
    return counts(cfg)['M'] * (
        4 * state_elements(cfg)
        + (cfg['conv_kernel'] - 1) * s['conv_dim'] * conv_itemsize)


def kv_bytes_per_token(cfg, itemsize: int = 2) -> int:
    return (2 * counts(cfg)['*'] * cfg['num_key_value_heads']
            * cfg['head_dim'] * itemsize)


def ssm_decode_work(cfg, slot_steps: float, steps: float,
                    itemsize: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of the ``M`` mixers over ``steps`` decode steps
    that advanced ``slot_steps`` slot states in all: a live slot's
    state and window are read and written once a block, its token
    passes ``W_in`` and ``W_out`` and the recurrence (5 operations a
    state element: decay, outer product, add, read through C); each
    step reads the two matrices once."""
    n, s = counts(cfg)['M'], sizes(cfg)
    per_slot_bytes = 2 * (4 * state_elements(cfg)
                          + (cfg['conv_kernel'] - 1) * s['conv_dim']
                          * itemsize)
    flops = n * slot_steps * (2.0 * mamba_matmul_params(cfg)
                              + 5.0 * state_elements(cfg))
    bytes_ = n * (slot_steps * per_slot_bytes
                  + steps * mamba_matmul_params(cfg) * itemsize)
    return flops, bytes_


def moe_experts_work(cfg, assignments: float, touched: float,
                     itemsize: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of the routed experts: every assignment to a held
    expert is one token through its two matrices; every (block, step)
    expert with a token is read once. Both counts are the program's
    counters, summed over steps and blocks."""
    return (2.0 * expert_params(cfg) * assignments,
            float(touched * expert_params(cfg) * itemsize))


def decode_flops(cfg, slot_steps: float, assignments: float,
                 context_sum: float) -> float:
    """Forward operations of decode steps that advanced ``slot_steps``
    live tokens in all: every block's matrices a live token, the
    recurrence, attention over ``context_sum`` keys, ``assignments``
    routed-expert passes, and the head over the vocabulary slice."""
    n = counts(cfg)
    per_token = (
        n['M'] * (2.0 * mamba_matmul_params(cfg) + 5.0 * state_elements(cfg))
        + n['*'] * 2.0 * attn_matmul_params(cfg)
        + n['E'] * 2.0 * moe_dense_params(cfg)
        + 2.0 * cfg['hidden_size'] * cfg['vocab_size'])
    attn = (4.0 * cfg['num_attention_heads'] * cfg['head_dim'] * n['*']
            * context_sum)
    return (slot_steps * per_token + attn
            + 2.0 * expert_params(cfg) * assignments)


def decode_weight_bytes(cfg, experts_touched_per_block: float,
                        itemsize: int = 2) -> float:
    """What one decode step reads of the weights when each ``E`` block
    has ``experts_touched_per_block`` of its held experts touched."""
    n, s = counts(cfg), sizes(cfg)
    return itemsize * (
        n['M'] * mamba_matmul_params(cfg) + n['*'] * attn_matmul_params(cfg)
        + n['E'] * (moe_dense_params(cfg)
                    + experts_touched_per_block * expert_params(cfg))
        + s['d'] * cfg['vocab_size'])
