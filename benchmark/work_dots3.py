"""Operations and bytes of the dots3-note step programs and of their new
mechanisms, from shapes and counts alone.

As ``work.py`` for the dense block: the work the *algorithm* needs,
counted with the benchmark so that it reads the same whatever
implements it. The selection makes the difference plain: a query of a
``full`` block scores every cached indexer key (2 x 64 x 128
operations a key) and then attends to the ``index_topk`` chosen rows
only, in the absorbed form (a head contracts its query with the cached
row, ``rank + rope`` wide, and sums ``rank`` columns); a ``sliding``
block attends to its window. Padded rows, slots that are not live,
experts that no live token reached, keys a masked dense pass would
have multiplied and thrown away: none is counted. ``cfg`` is the
configuration file's dict (published key names; ``n_routed_experts``
is what this share holds).
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, Tuple

from benchmark.weights_dots3 import attn_sizes, block_kinds
from benchmark.work import peaks, roofline_share  # noqa: F401  (re-used)


def counts(cfg: Dict[str, Any]) -> Dict[str, int]:
    kinds = block_kinds(cfg)
    return {'full': sum(k == 'full' for k, _ in kinds),
            'sliding': sum(k == 'sliding' for k, _ in kinds),
            'dense': sum(d for _, d in kinds),
            'moe': sum(not d for _, d in kinds)}


def attn_params(cfg, kind: str) -> int:
    """The matrices of one block's attention, the indexer's among them
    (norm vectors apart)."""
    s, d = attn_sizes(cfg, kind), cfg['hidden_size']
    n = (d * s['q_rank'] + s['q_rank'] * s['heads'] * (s['nope'] + s['rope'])
         + d * (s['kv_rank'] + s['rope'])
         + s['kv_rank'] * s['heads'] * (s['nope'] + s['v'])
         + d * s['heads'] + s['heads'] * s['v'] * d)
    if kind == 'full':
        n += index_params(cfg)
    return n


def index_params(cfg) -> int:
    j, di = cfg['index_n_heads'], cfg['index_head_dim']
    return (cfg['q_lora_rank'] * j * di + cfg['hidden_size'] * di
            + cfg['hidden_size'] * j)


def attn_vectors(cfg, kind: str) -> int:
    s = attn_sizes(cfg, kind)
    n = cfg['hidden_size'] + s['q_rank'] + s['kv_rank']
    return n + (2 * cfg['index_head_dim'] if kind == 'full' else 0)


def expert_params(cfg) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg['hidden_size'] * cfg['moe_intermediate_size']


def moe_dense_params(cfg) -> int:
    """What every token of an expert block passes: router and shared."""
    return (cfg['hidden_size'] * cfg['n_routed_experts_published']
            + cfg['n_shared_experts'] * expert_params(cfg))


def dense_mlp_params(cfg) -> int:
    return 3 * cfg['hidden_size'] * cfg['intermediate_size']


def block_params(cfg, kind: str, dense: bool) -> int:
    d = cfg['hidden_size']
    second = (dense_mlp_params(cfg) if dense else
              cfg['n_routed_experts'] * expert_params(cfg)
              + moe_dense_params(cfg) + cfg['n_routed_experts_published'])
    return attn_params(cfg, kind) + attn_vectors(cfg, kind) + second + d


def total_params(cfg) -> int:
    d = cfg['hidden_size']
    return (sum(block_params(cfg, k, dense) for k, dense in block_kinds(cfg))
            + 2 * d * cfg['vocab_size'] + d)


def token_matmul_params(cfg) -> int:
    """Matrix parameters ONE token passes outside the routed experts
    and the head: every block's attention, the dense MLP, each expert
    block's router and shared expert."""
    n = counts(cfg)
    return (n['full'] * attn_params(cfg, 'full')
            + n['sliding'] * attn_params(cfg, 'sliding')
            + n['dense'] * dense_mlp_params(cfg)
            + n['moe'] * moe_dense_params(cfg))


def cache_bytes_per_token(cfg, itemsize: int = 2) -> int:
    """What one token leaves in the GROWING pools: a latent row and an
    indexer key in every full block."""
    s = attn_sizes(cfg, 'full')
    return counts(cfg)['full'] * itemsize * (
        s['kv_rank'] + s['rope'] + cfg['index_head_dim'])


def window_row_bytes(cfg, itemsize: int = 2) -> int:
    s = attn_sizes(cfg, 'sliding')
    return (s['kv_rank'] + s['rope']) * itemsize


# ---- the selection and the attention over what it keeps -----------------

def scored_keys(tokens: int, offset: int) -> float:
    """Keys the queries at ``offset .. offset + tokens`` score in ONE
    full block: query ``t`` scores ``t + 1``."""
    return tokens * offset + tokens * (tokens + 1) / 2.0


def selected_keys(cfg, tokens: int, offset: int) -> float:
    """Of those, the rows they attend to: ``min(t + 1, index_topk)``."""
    k = cfg['index_topk']
    under = max(0, min(tokens, k - offset))       # queries with t + 1 <= k
    return (under * offset + under * (under + 1) / 2.0
            + (tokens - under) * float(k))


def window_keys(cfg, tokens: int, offset: int) -> float:
    """Keys the same queries attend to in ONE sliding block."""
    w = cfg['sliding_window_size']
    under = max(0, min(tokens, w - offset))
    return (under * offset + under * (under + 1) / 2.0
            + (tokens - under) * float(w))


def index_flops(cfg, scored: float) -> float:
    """Scoring ``scored`` (query, key) pairs: 2 x J x di operations
    each."""
    return 2.0 * cfg['index_n_heads'] * cfg['index_head_dim'] * scored


def absorbed_attention_flops(cfg, kind: str, keys: float) -> float:
    """Attention over ``keys`` (query, row) pairs in the absorbed form:
    a head contracts ``rank + rope`` for the score and sums ``rank``."""
    s = attn_sizes(cfg, kind)
    return 2.0 * s['heads'] * (2 * s['kv_rank'] + s['rope']) * keys


def absorb_flops(cfg, kind: str, tokens: float) -> float:
    """Folding ``W_uk`` into the query and ``W_uv`` out of the sum, a
    token: the price of the absorbed form beside the projections."""
    s = attn_sizes(cfg, kind)
    return 2.0 * tokens * s['heads'] * s['kv_rank'] * (s['nope'] + s['v'])


def attn_scope_work(cfg, chunks: Iterable[Tuple[int, int]],
                    itemsize: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of everything the prefill-chunk program does under
    scope ``attn`` for ``chunks`` = (tokens, offset), all blocks: the
    projections (each block's attention matrices read once a chunk),
    the indexer's scores over the whole context (every cached key read
    once a chunk), attention over the chosen rows (each chosen row read
    once a query: the rows differ from query to query) and over the
    window (its rows read once a chunk)."""
    n = counts(cfg)
    full, win = attn_sizes(cfg, 'full'), attn_sizes(cfg, 'sliding')
    # W_uk / W_uv act through absorb_flops, not as a product with the
    # hidden stream: their parameters come out of the 2 x params.
    folded = {k: z['kv_rank'] * z['heads'] * (z['nope'] + z['v'])
              for k, z in (('full', full), ('sliding', win))}
    flops = bytes_ = 0.0
    for c, off in chunks:
        scored, chosen = scored_keys(c, off), selected_keys(cfg, c, off)
        seen = window_keys(cfg, c, off)
        flops += n['full'] * (
            2.0 * c * (attn_params(cfg, 'full') - folded['full'])
            + absorb_flops(cfg, 'full', c) + index_flops(cfg, scored)
            + absorbed_attention_flops(cfg, 'full', chosen))
        flops += n['sliding'] * (
            2.0 * c * (attn_params(cfg, 'sliding') - folded['sliding'])
            + absorb_flops(cfg, 'sliding', c)
            + absorbed_attention_flops(cfg, 'sliding', seen))
        bytes_ += itemsize * (
            n['full'] * (attn_params(cfg, 'full')
                         + (off + c) * cfg['index_head_dim']
                         + chosen * (full['kv_rank'] + full['rope']))
            + n['sliding'] * (attn_params(cfg, 'sliding')
                              + min(off + c, c + cfg['sliding_window_size'])
                              * (win['kv_rank'] + win['rope'])))
    return flops, bytes_


def prefill_flops(cfg, chunks: Iterable[Tuple[int, int]],
                  assignments_per_token: float) -> float:
    """Forward operations of the prefill-chunk program over ``chunks``:
    the ``attn`` scope's, the dense MLP, router and shared expert a
    token, ``assignments_per_token`` routed-expert passes a token and
    expert block (what reached THIS share's experts), and the head on
    one row a chunk."""
    chunks = list(chunks)
    n = counts(cfg)
    tokens = float(sum(c for c, _ in chunks))
    return (attn_scope_work(cfg, chunks)[0]
            + 2.0 * tokens * (n['dense'] * dense_mlp_params(cfg)
                              + n['moe'] * moe_dense_params(cfg))
            + 2.0 * expert_params(cfg) * assignments_per_token * tokens
            * n['moe']
            + 2.0 * cfg['hidden_size'] * cfg['vocab_size'] * len(chunks))


def decode_flops(cfg, contexts: Iterable[int],
                 assignments_per_token: float) -> float:
    """Forward operations of decode steps that advanced one token for
    each of ``contexts`` (keys up to and with the new token): every
    block's matrices, the indexer over the context, attention over the
    chosen rows and the window, the routed passes, the head."""
    ctx = list(contexts)
    n = counts(cfg)
    flops = 0.0
    for c in ctx:
        flops += attn_scope_work(cfg, [(1, c - 1)])[0]
    return (flops + len(ctx) * (
        2.0 * (n['dense'] * dense_mlp_params(cfg)
               + n['moe'] * moe_dense_params(cfg))
        + 2.0 * expert_params(cfg) * assignments_per_token * n['moe']
        + 2.0 * cfg['hidden_size'] * cfg['vocab_size']))


def selected_attention_work(cfg, chunks: Iterable[Tuple[int, int]],
                            contexts: Iterable[int], itemsize: int = 2
                            ) -> Tuple[float, float]:
    """(flops, bytes) of attention over the CHOSEN rows alone, all full
    blocks, for prefill ``chunks`` = (tokens, offset) and decode tokens
    at ``contexts``: the absorbed form's operations a (query, chosen
    row), each chosen row read once a query (the rows differ from query
    to query), the folded queries read and the latent sums written
    (float32). Rows a pass reads and masks away are not counted."""
    s = attn_sizes(cfg, 'full')
    chunks, ctx = list(chunks), list(contexts)
    chosen = (sum(selected_keys(cfg, c, off) for c, off in chunks)
              + sum(float(min(c, cfg['index_topk'])) for c in ctx))
    queries = sum(c for c, _ in chunks) + len(ctx)
    row = s['kv_rank'] + s['rope']
    n = counts(cfg)['full']
    return (n * absorbed_attention_flops(cfg, 'full', chosen),
            float(n * (itemsize * chosen * row + queries * s['heads']
                       * (itemsize * row + 4 * s['kv_rank']))))


def index_scores_work(cfg, chunks: Iterable[Tuple[int, int]],
                      itemsize: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of the indexer's scores for prefill ``chunks`` =
    (tokens, offset), all full blocks: 2 x J x di operations a scored
    pair; each cached key read once a chunk, the chunk's queries and
    head weights read, one float32 score a pair written."""
    j, di = cfg['index_n_heads'], cfg['index_head_dim']
    flops = bytes_ = 0.0
    for c, off in chunks:
        scored = scored_keys(c, off)
        flops += index_flops(cfg, scored)
        bytes_ += (itemsize * ((off + c) * di + c * j * di) + 4 * c * j
                   + 4 * scored)
    n = counts(cfg)['full']
    return n * flops, n * bytes_


def gated_experts_work(cfg, assignments: float, touched: float,
                       itemsize: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of the routed experts: every assignment to a held
    expert is one token through its three matrices; every (block, step)
    expert with a token is read once. Both counts are the program's
    counters, summed over steps and blocks."""
    return (2.0 * expert_params(cfg) * assignments,
            float(touched * expert_params(cfg) * itemsize))
