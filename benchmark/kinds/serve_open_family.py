"""Kind ``serve_open_family``: ``serve_open`` for a model that a family
module describes (see ``_serve_family.py``)."""
from __future__ import annotations

from typing import Any, Dict

from benchmark.kinds import _serve_family


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    if ctx['traffic']['loop'] != 'open':
        raise ValueError(f'kind serve_open_family needs an open-loop traffic '
                         f'mix, got {ctx["traffic"]["loop"]!r}')
    return _serve_family.run(ctx)
