"""Operations and bytes of the model's step and of each kernel, from
shapes alone.

A roofline or a utilization divides the work the *algorithm* needs by a
time and a peak. The work is counted here, with the benchmark, so that
it reads the same whatever implements the kernel: recomputed, padded or
masked-out work is not counted, and a later PR cannot change the count.
``cfg`` is a configuration file's dict (the published key names).
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, Iterable, Tuple

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> Dict[str, Any]:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(os.path.join(_HERE, 'peaks.json'), encoding='utf-8') as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f'no published peaks for device kind {device_kind!r} in '
            f'benchmark/peaks.json (have: {sorted(table)})')
    return table[device_kind]


def layer_matmul_params(cfg: Dict[str, Any]) -> int:
    d, f, hd = cfg['hidden_size'], cfg['intermediate_size'], cfg['head_dim']
    q, kv = cfg['num_attention_heads'] * hd, cfg['num_key_value_heads'] * hd
    return d * q + 2 * d * kv + q * d + 3 * d * f


def head_params(cfg: Dict[str, Any]) -> int:
    return cfg['hidden_size'] * cfg['vocab_size']


def total_params(cfg: Dict[str, Any]) -> int:
    """Every parameter: the layers' matrices and norms, the embedding,
    the final norm and the untied head."""
    d = cfg['hidden_size']
    return (cfg['num_hidden_layers'] * (layer_matmul_params(cfg) + 2 * d)
            + 2 * head_params(cfg) + d)


def attention_flops(cfg: Dict[str, Any], context_sum: float) -> float:
    """Forward attention over ``context_sum`` = the sum, over query
    tokens, of the keys each attends to: QK^T and PV, 2 * head_dim each
    a head and key, in every layer."""
    return (4.0 * cfg['num_attention_heads'] * cfg['head_dim']
            * cfg['num_hidden_layers'] * context_sum)


def forward_flops(cfg: Dict[str, Any], tokens: float, context_sum: float,
                  head_rows: float) -> float:
    """One forward pass: ``tokens`` rows through every layer's matrices,
    their attention, and the head on the ``head_rows`` rows whose logits
    the algorithm needs (one a request in prefill, every row in decode
    and in training)."""
    return (2.0 * cfg['num_hidden_layers'] * layer_matmul_params(cfg) * tokens
            + attention_flops(cfg, context_sum)
            + 2.0 * head_params(cfg) * head_rows)


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward and backward of one token in a causal sequence of
    ``seq``: three times the forward pass; recomputation not counted."""
    return 3.0 * forward_flops(cfg, 1.0, (seq + 1) / 2.0, 1.0)


def kv_bytes_per_token(cfg: Dict[str, Any], itemsize: int = 2) -> int:
    """Keys and values of one token in every layer."""
    return (2 * cfg['num_hidden_layers'] * cfg['num_key_value_heads']
            * cfg['head_dim'] * itemsize)


def weight_stream_bytes(cfg: Dict[str, Any], itemsize: int = 1) -> int:
    """What one decode step reads of the weights: every layer's matrices
    and the head (the embedding is gathered by row)."""
    return (cfg['num_hidden_layers'] * layer_matmul_params(cfg)
            + head_params(cfg)) * itemsize


def paged_decode_work(cfg: Dict[str, Any], contexts: Iterable[int],
                      page: int, itemsize: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of the paged decode attention kernel over all
    layers for decode tokens whose contexts (keys attended, the new
    token's own among them) are ``contexts``: each reads its context's
    pages whole and writes one row of output a head."""
    ctx = list(contexts)
    qo = (2 * cfg['num_attention_heads'] * cfg['head_dim'] * itemsize
          * cfg['num_hidden_layers'] * len(ctx))
    paged = sum(-(-c // page) * page for c in ctx)
    return (attention_flops(cfg, float(sum(ctx))),
            float(paged * kv_bytes_per_token(cfg, itemsize) + qo))


def paged_prefill_work(cfg: Dict[str, Any],
                       chunks: Iterable[Tuple[int, int]],
                       itemsize: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of the paged prefill attention kernel over all
    layers for ``chunks`` = (tokens, offset): each token attends to the
    offset and causally to its chunk; the kernel reads the keys and
    values up to the chunk's end once and reads and writes the chunk's
    queries and outputs."""
    flops = bytes_ = 0.0
    for c, off in chunks:
        flops += attention_flops(cfg, c * off + c * (c + 1) / 2.0)
        bytes_ += ((off + c) * kv_bytes_per_token(cfg, itemsize)
                   + 2 * c * cfg['num_attention_heads'] * cfg['head_dim']
                   * itemsize * cfg['num_hidden_layers'])
    return flops, bytes_


def roofline_share(flops: float, bytes_: float, seconds: float,
                   peak: Dict[str, Any],
                   flops_key: str = 'bf16_flops_per_s') -> Dict[str, Any]:
    """The least time the chip could take (the larger of operations over
    peak rate and bytes over peak bandwidth) over the time taken, in
    percent, and which of the two bounds it."""
    t_flops = flops / peak[flops_key]
    t_bytes = bytes_ / peak['hbm_bytes_per_s']
    return {'percent': 100.0 * max(t_flops, t_bytes) / seconds,
            'bound': 'compute' if t_flops >= t_bytes else 'memory'}
