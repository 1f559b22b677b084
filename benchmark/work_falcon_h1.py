"""Operations and bytes of the Falcon-H1 parallel block's two step
programs, of its mixer's scope and of the paged kernels at its 20 / 4
heads, from shapes and counts alone.

As ``work.py`` for the dense block: the work the *algorithm* needs,
counted with the benchmark so that it reads the same whatever
implements it. Padded rows, slots that are not live and context that
is not attended are not counted. ``cfg`` is the configuration file's
dict (published key names). The attention kernels' counts are
``work.py``'s own, which read the same keys (every block attends).
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, Tuple

from benchmark.weights_falcon_h1 import sizes
from benchmark.work import (  # noqa: F401  (re-used)
    attention_flops, kv_bytes_per_token, paged_decode_work,
    paged_prefill_work, peaks, roofline_share)


def attn_matmul_params(cfg) -> int:
    s = sizes(cfg)
    return 2 * s['d'] * s['q'] + 2 * s['d'] * s['kv']


def mixer_matmul_params(cfg) -> int:
    s = sizes(cfg)
    return s['d'] * s['in_proj'] + s['d_inner'] * s['d']


def mlp_matmul_params(cfg) -> int:
    s = sizes(cfg)
    return 3 * s['d'] * s['f']


def block_matmul_params(cfg) -> int:
    return (attn_matmul_params(cfg) + mixer_matmul_params(cfg)
            + mlp_matmul_params(cfg))


def head_params(cfg) -> int:
    return cfg['hidden_size'] * cfg['vocab_size']


def total_params(cfg) -> int:
    """Every parameter: the blocks' matrices, convolutions, per-head
    vectors and norms; the embedding, the final norm, the untied head."""
    s = sizes(cfg)
    small = ((cfg['mamba_d_conv'] + 1) * s['conv_dim']
             + 3 * cfg['mamba_n_heads'] + s['d_inner'] + 2 * s['d'])
    return (cfg['num_hidden_layers'] * (block_matmul_params(cfg) + small)
            + 2 * head_params(cfg) + s['d'])


def state_elements(cfg) -> int:
    """One slot's SSM state in one block."""
    return cfg['mamba_n_heads'] * cfg['mamba_d_head'] * cfg['mamba_d_state']


def state_bytes_per_slot(cfg, conv_itemsize: int = 2) -> int:
    return cfg['num_hidden_layers'] * (
        4 * state_elements(cfg)
        + (cfg['mamba_d_conv'] - 1) * sizes(cfg)['conv_dim'] * conv_itemsize)


def _token_flops(cfg) -> float:
    """One token through every block's matrices and recurrence (5
    operations a state element: decay, outer product, add, read
    through C)."""
    return cfg['num_hidden_layers'] * (
        2.0 * block_matmul_params(cfg) + 5.0 * state_elements(cfg))


def ssm_decode_work(cfg, slot_steps: float, steps: float,
                    itemsize: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of the mixers over ``steps`` decode steps that
    advanced ``slot_steps`` slot states in all: a live slot's state and
    window are read and written once a block, its token passes ``W_in``
    and ``W_out`` and the recurrence; each step reads the two matrices
    once."""
    n = cfg['num_hidden_layers']
    per_slot_bytes = 2 * state_bytes_per_slot(cfg, itemsize) / n
    flops = n * slot_steps * (2.0 * mixer_matmul_params(cfg)
                              + 5.0 * state_elements(cfg))
    bytes_ = n * (slot_steps * per_slot_bytes
                  + steps * mixer_matmul_params(cfg) * itemsize)
    return flops, bytes_


def decode_flops(cfg, slot_steps: float, context_sum: float) -> float:
    """Forward operations of decode steps that advanced ``slot_steps``
    live tokens in all, attending to ``context_sum`` keys: every
    block's matrices and recurrence a live token, attention, and the
    head over the vocabulary slice."""
    return (slot_steps * (_token_flops(cfg) + 2.0 * head_params(cfg))
            + attention_flops(cfg, context_sum))


def prefill_flops(cfg, chunks: Iterable[Tuple[int, int]]) -> float:
    """Forward operations of prefill ``chunks`` = (tokens, offset):
    every block's matrices and recurrence a token, causal attention
    over the offset and the chunk, and the head on one row a chunk."""
    flops = 0.0
    for c, off in chunks:
        flops += (c * _token_flops(cfg)
                  + attention_flops(cfg, c * off + c * (c + 1) / 2.0)
                  + 2.0 * head_params(cfg))
    return flops


def decode_weight_bytes(cfg, itemsize: int = 2) -> float:
    """What one decode step reads of the weights: every block's
    matrices and the head (the embedding is gathered by row)."""
    return itemsize * (cfg['num_hidden_layers'] * block_matmul_params(cfg)
                       + head_params(cfg))
