"""Kind ``serve_open``: one engine behind the real server and load
balancer under open-loop traffic (see ``_serve.py``)."""
from __future__ import annotations

from typing import Any, Dict

from benchmark.kinds import _serve


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    if ctx['traffic']['loop'] != 'open':
        raise ValueError(f'kind serve_open needs a open-loop traffic mix, got '
                         f'{ctx["traffic"]["loop"]!r}')
    return _serve.run(ctx)
