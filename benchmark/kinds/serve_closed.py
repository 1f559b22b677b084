"""Kind ``serve_closed``: one engine behind the real server and load
balancer under closed-loop traffic (see ``_serve.py``)."""
from __future__ import annotations

from typing import Any, Dict

from benchmark.kinds import _serve


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    if ctx['traffic']['loop'] != 'closed':
        raise ValueError(f'kind serve_closed needs a closed-loop traffic mix, got '
                         f'{ctx["traffic"]["loop"]!r}')
    return _serve.run(ctx)
