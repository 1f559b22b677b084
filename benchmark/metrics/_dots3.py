"""What the dots3 cell's readers share: ``_common``'s helpers, and the
routed passes a token of the traced stretch."""
from __future__ import annotations

from typing import Any, Dict, Optional

from benchmark import work_dots3
from benchmark.metrics._common import (  # noqa: F401  (the readers')
    counter_delta, own_file, traced_decode_contexts, traced_prefill_chunks)


def assignments_per_token(run: Dict[str, Any]) -> Optional[float]:
    """Routed-expert passes that reached THIS share, a token and expert
    block, over the traced stretch: ``moe_local_assignments`` counts
    prefill chunks and decode steps alike, so the tokens are both's."""
    assignments = counter_delta(run, 'moe_local_assignments', traced=True)
    tokens = (sum(c for c, _ in traced_prefill_chunks(run))
              + len(traced_decode_contexts(run)))
    if assignments is None or not tokens:
        return None
    blocks = work_dots3.counts(run['config'])['moe']
    return assignments / (tokens * blocks) if blocks else None
