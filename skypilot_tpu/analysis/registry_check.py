"""SKY-REGISTRY: code↔docs catalog sync for failpoints and metrics.

Two registries drive operability and MUST NOT drift from their docs:

1. **Failpoint sites** — every ``failpoints.hit('x')`` /
   ``hit_async('x')`` call site in the package must appear in
   docs/robustness.md's "Site catalog" table, and every cataloged
   site must still exist in code. An undocumented site is a chaos
   hook nobody can find; a documented ghost site is a chaos spec that
   silently injects nothing (exactly the failure mode the failpoint
   module's loud spec errors exist to prevent).

2. **Serving metric keys** — every key emitted by the serving metric
   surfaces (``InferenceEngine.metrics`` / ``EnginePool.metrics``,
   ``PrefixCache.stats``, the infer server's ``h_metrics`` additions,
   the LB's ``lb_metrics``) must appear in docs/observability.md's
   "Serving metrics" catalog tables, and vice versa. Dashboards and
   the benchmark's readers are built on these names; a renamed key is
   a silently-flatlined graph.

Doc format contract: catalog entries are markdown table rows whose
first cell is the backticked name —  ``| `site.name` | ... |`` —
inside the "### Site catalog" section (robustness.md) or the
"## Serving metrics" section (observability.md).

The doc→code direction only runs on a full-package scan (a partial
``sky-tpu lint path`` cannot see every call site, so "documented but
not found" would false-fire). Doc-side findings use the path
``docs/<file>`` so allowlist keys stay uniform.
"""
from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from skypilot_tpu.analysis import core
from skypilot_tpu.analysis import walker

# Functions whose dict-literal keys / subscript-assignment keys form
# the serving-metrics namespace: (module rel path, function name).
METRIC_FUNCS: Tuple[Tuple[str, str], ...] = (
    ('infer/engine.py', 'metrics'),
    ('infer/prefix_cache.py', 'stats'),
    ('infer/sched/base.py', 'aggregate_stats'),
    ('infer/server.py', 'h_metrics'),
    ('serve/load_balancer.py', 'lb_metrics'),
)

# Functions whose string literals starting with the exposition prefix
# name Prometheus metric families (observability/prometheus.py's
# curated maps): every family must appear in docs/observability.md's
# "## Prometheus exposition" catalog, both directions — a renamed
# family is a silently-flatlined scrape.
EXPOSITION_FUNCS: Tuple[Tuple[str, str], ...] = (
    ('observability/prometheus.py', 'lb_exposition'),
    ('observability/prometheus.py', 'replica_exposition'),
    ('observability/prometheus.py', 'label_families'),
)
EXPOSITION_PREFIX = 'sky_tpu_'

_ROW_RE = re.compile(r'^\|\s*`([^`]+)`')


def _doc_section_names(docs_root: str, fname: str, heading: str
                       ) -> Optional[Tuple[Set[str], Dict[str, int]]]:
    """Backticked first-cell names of table rows inside ``heading``'s
    section. Returns (names, name->line) or None when the doc or the
    section is missing."""
    path = os.path.join(docs_root, fname)
    if not os.path.isfile(path):
        return None
    with open(path, encoding='utf-8') as f:
        lines = f.read().splitlines()
    level = heading.split(' ', 1)[0]     # '##' or '###'
    names: Set[str] = set()
    where: Dict[str, int] = {}
    in_section = False
    for i, line in enumerate(lines, 1):
        if line.strip() == heading:
            in_section = True
            continue
        if in_section and line.startswith('#'):
            hashes = line.split(' ', 1)[0]
            if len(hashes) <= len(level):
                break
        if not in_section:
            continue
        m = _ROW_RE.match(line.strip())
        if m:
            name = m.group(1)
            names.add(name)
            where.setdefault(name, i)
    if not in_section:
        return None
    return names, where


class RegistryChecker(core.Checker):
    code = 'SKY-REGISTRY'
    title = ('failpoint sites and serving-metric keys stay in sync '
             'with the docs catalogs')

    def check(self, files: Sequence[core.SourceFile],
              ctx: core.RunContext) -> Iterable[core.Finding]:
        if ctx.docs_root is None:
            return
        yield from self._check_failpoints(files, ctx)
        yield from self._check_metrics(files, ctx)
        yield from self._check_exposition(files, ctx)

    # -- failpoint sites ---------------------------------------------------
    def _failpoint_sites(self, files: Sequence[core.SourceFile]
                         ) -> List[Tuple[str, str, int]]:
        sites: List[Tuple[str, str, int]] = []
        for src in files:
            for node in ast.walk(src.tree):
                if not isinstance(node, ast.Call):
                    continue
                name = walker.call_name(node)
                if name is None:
                    continue
                leaf = name.rsplit('.', 1)[-1]
                if leaf not in ('hit', 'hit_async'):
                    continue
                if '.' in name and not name.startswith('failpoints'):
                    # someone_else.hit() — only the failpoints module
                    # (or a direct import of its functions) counts.
                    continue
                if (node.args
                        and isinstance(node.args[0], ast.Constant)
                        and isinstance(node.args[0].value, str)):
                    sites.append((node.args[0].value, src.rel,
                                  node.lineno))
        return sites

    def _check_failpoints(self, files: Sequence[core.SourceFile],
                          ctx: core.RunContext
                          ) -> Iterable[core.Finding]:
        doc = _doc_section_names(ctx.docs_root, 'robustness.md',
                                 '### Site catalog')
        if doc is None:
            if ctx.full_package:
                yield core.Finding(
                    self.code, 'docs/robustness.md', 0,
                    'failpoint "### Site catalog" section not found '
                    '— the chaos-site registry has no docs anchor')
            return
        documented, where = doc
        sites = self._failpoint_sites(files)
        for site, rel, lineno in sites:
            if site not in documented:
                yield core.Finding(
                    self.code, rel, lineno,
                    f'failpoint site {site!r} is not in '
                    f'docs/robustness.md\'s site catalog — an '
                    f'undocumented chaos hook nobody can arm')
        if ctx.full_package:
            in_code = {s for s, _, _ in sites}
            for site in sorted(documented - in_code):
                yield core.Finding(
                    self.code, 'docs/robustness.md',
                    where.get(site, 0),
                    f'cataloged failpoint site {site!r} has no '
                    f'hit()/hit_async() call site left in the '
                    f'package — a chaos spec naming it silently '
                    f'injects nothing')

    # -- serving metric keys -----------------------------------------------
    @staticmethod
    def _metric_keys(files: Sequence[core.SourceFile]
                     ) -> List[Tuple[str, str, int]]:
        by_rel = {s.rel: s for s in files}
        keys: List[Tuple[str, str, int]] = []
        for rel, fn_name in METRIC_FUNCS:
            src = by_rel.get(rel)
            if src is None:
                continue
            for node in ast.walk(src.tree):
                if not isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                    continue
                if node.name != fn_name:
                    continue
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Dict):
                        for k in sub.keys:
                            if (isinstance(k, ast.Constant)
                                    and isinstance(k.value, str)):
                                keys.append((k.value, rel, k.lineno))
                    elif (isinstance(sub, ast.Subscript)
                          and isinstance(sub.ctx, ast.Store)
                          and isinstance(sub.slice, ast.Constant)
                          and isinstance(sub.slice.value, str)):
                        keys.append((sub.slice.value, rel,
                                     sub.lineno))
        return keys

    def _check_metrics(self, files: Sequence[core.SourceFile],
                       ctx: core.RunContext) -> Iterable[core.Finding]:
        relevant = {rel for rel, _ in METRIC_FUNCS}
        scanned = {s.rel for s in files}
        if not relevant & scanned:
            return   # partial scan with no metric surface in it
        doc = _doc_section_names(ctx.docs_root, 'observability.md',
                                 '## Serving metrics')
        if doc is None:
            yield core.Finding(
                self.code, 'docs/observability.md', 0,
                'serving-metrics catalog ("## Serving metrics") not '
                'found in docs/observability.md')
            return
        documented, where = doc
        keys = self._metric_keys(files)
        seen: Set[Tuple[str, str]] = set()
        for key, rel, lineno in keys:
            if key in documented or (key, rel) in seen:
                continue
            seen.add((key, rel))
            yield core.Finding(
                self.code, rel, lineno,
                f'metric key {key!r} is not in '
                f'docs/observability.md\'s serving-metrics catalog '
                f'— dashboards cannot discover it')
        if ctx.full_package:
            in_code = {k for k, _, _ in keys}
            for key in sorted(documented - in_code):
                yield core.Finding(
                    self.code, 'docs/observability.md',
                    where.get(key, 0),
                    f'cataloged metric key {key!r} is no longer '
                    f'emitted by any serving metric surface — a '
                    f'dashboard graphing it has flatlined')

    # -- Prometheus exposition families --------------------------------------
    @staticmethod
    def _exposition_families(files: Sequence[core.SourceFile]
                             ) -> List[Tuple[str, str, int]]:
        """Every ``sky_tpu_*`` string literal inside the curated
        exposition maps — the family namespace a scrape sees."""
        by_rel = {s.rel: s for s in files}
        fams: List[Tuple[str, str, int]] = []
        for rel, fn_name in EXPOSITION_FUNCS:
            src = by_rel.get(rel)
            if src is None:
                continue
            for node in ast.walk(src.tree):
                if not isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                    continue
                if node.name != fn_name:
                    continue
                for sub in ast.walk(node):
                    if (isinstance(sub, ast.Constant)
                            and isinstance(sub.value, str)
                            and sub.value.startswith(
                                EXPOSITION_PREFIX)):
                        fams.append((sub.value, rel, sub.lineno))
        return fams

    def _check_exposition(self, files: Sequence[core.SourceFile],
                          ctx: core.RunContext
                          ) -> Iterable[core.Finding]:
        relevant = {rel for rel, _ in EXPOSITION_FUNCS}
        if not relevant & {s.rel for s in files}:
            return   # partial scan without the exposition module
        doc = _doc_section_names(ctx.docs_root, 'observability.md',
                                 '## Prometheus exposition')
        if doc is None:
            yield core.Finding(
                self.code, 'docs/observability.md', 0,
                'Prometheus exposition catalog ("## Prometheus '
                'exposition") not found in docs/observability.md')
            return
        documented, where = doc
        fams = self._exposition_families(files)
        seen: Set[str] = set()
        for fam, rel, lineno in fams:
            if fam in documented or fam in seen:
                continue
            seen.add(fam)
            yield core.Finding(
                self.code, rel, lineno,
                f'exposition family {fam!r} is not in '
                f'docs/observability.md\'s Prometheus exposition '
                f'catalog — scrape configs cannot discover it')
        if ctx.full_package:
            in_code = {f for f, _, _ in fams}
            for fam in sorted(documented - in_code):
                yield core.Finding(
                    self.code, 'docs/observability.md',
                    where.get(fam, 0),
                    f'cataloged exposition family {fam!r} is no '
                    f'longer emitted — a dashboard scraping it has '
                    f'flatlined')
