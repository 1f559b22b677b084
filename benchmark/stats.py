"""The arithmetic from client records to numbers, shared by the
end-to-end metrics and the per-layer readers."""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional


def percentile(values: List[float], p: float) -> Optional[float]:
    """Nearest rank: the smallest value with at least ``p`` of the
    sample at or below it; ``None`` of an empty sample."""
    if not values:
        return None
    ordered = sorted(values)
    return ordered[max(0, math.ceil(p * len(ordered)) - 1)]


def token_gaps(records: List[Dict[str, Any]]) -> List[float]:
    """Every gap between token arrivals, all requests pooled: a line of
    k tokens gives k gaps of 1/k of the time since the line before (the
    first line of a request gives none)."""
    gaps: List[float] = []
    for r in records:
        for (t_prev, _), (t, k) in zip(r['arrivals'], r['arrivals'][1:]):
            gaps.extend([(t - t_prev) / k] * k)
    return gaps
