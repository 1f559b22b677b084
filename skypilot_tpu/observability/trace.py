"""Trace context + span recorder (the wire-crossing half of tracing).

Contract (mirrors the Dapper/W3C trace-context model):

- A **trace** is one logical request's tree of **spans**; every span
  carries ``(trace_id, span_id, parent_id)``. Context rides between
  processes as a W3C-style ``traceparent`` string
  (``00-<32 hex>-<16 hex>-01``) in three channels: the ``traceparent``
  HTTP header (SDK→server, server→agent, LB→replica), the
  ``SKY_TPU_TRACEPARENT`` env var (parent → child process, e.g. agent →
  job ranks), and the ``_traceparent`` request-payload field (API
  server → its detached request worker, via the persisted request row).
- **Zero overhead when disabled**: ``SKY_TPU_TRACE`` unset means
  ``traced`` returns the original function at decoration time,
  ``span()`` yields without allocating, and ``inject_headers`` is a
  no-op. Nothing is buffered, nothing is shipped.
- **Fail-open**: recording and shipping must never fail a request.
  Every ship path swallows errors; the buffer is size-capped and drops
  (never blocks) when full.

Finished spans buffer in-process and ship on ``flush()`` (driven by a
background shipper thread and atexit — never synchronously from the
recording thread, which may be an event loop): to a collector URL when
one is resolvable
(``SKY_TPU_TRACE_COLLECTOR``, then ``SKY_TPU_API_SERVER``, then the
local ``api_server.json``), else straight into the local span store.
The API server short-circuits by installing a local sink
(``set_sink``), so its own spans never loop through HTTP.
"""
from __future__ import annotations

import atexit
import contextlib
import contextvars
import functools
import os
import re
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

ENV_VAR = 'SKY_TPU_TRACE'
CTX_ENV_VAR = 'SKY_TPU_TRACEPARENT'
COLLECTOR_ENV_VAR = 'SKY_TPU_TRACE_COLLECTOR'
# Collector URL as reachable FROM provisioned cluster hosts (the API
# server's VPC/ingress address) — stamped into agent_config.json so
# remote agents can ship their spans home.
AGENT_COLLECTOR_ENV_VAR = 'SKY_TPU_TRACE_AGENT_COLLECTOR'
PAYLOAD_KEY = '_traceparent'
HEADER = 'traceparent'

# Buffer cap: a hot instrumented loop must not grow RAM
# without bound if shipping stalls; drops are counted, not silent.
_MAX_BUFFER = 10_000

_TRACEPARENT_RE = re.compile(
    r'^00-([0-9a-f]{32})-([0-9a-f]{16})-[0-9a-f]{2}$')

_current: contextvars.ContextVar[Optional['SpanContext']] = (
    contextvars.ContextVar('sky_tpu_trace_ctx', default=None))

_buffer: List[Dict[str, Any]] = []
_buffer_lock = threading.Lock()
_dropped = 0
_flush_registered = False
_sink: Optional[Callable[[List[Dict[str, Any]]], Any]] = None
_hop: Optional[str] = None
# Background shipper: spans must never be flushed synchronously from
# the recording thread — span closure happens on aiohttp event loops
# (the API server's admission span, the LB's proxy span), and a flush
# is sqlite or HTTP I/O. A daemon thread drains the buffer instead.
_SHIP_INTERVAL_S = 0.3
_shipper_started = False
_shipper_lock = threading.Lock()


class SpanContext:
    """(trace_id, span_id) pair — the propagated identity of a span."""

    __slots__ = ('trace_id', 'span_id')

    def __init__(self, trace_id: str, span_id: str) -> None:
        self.trace_id = trace_id
        self.span_id = span_id

    def traceparent(self) -> str:
        return f'00-{self.trace_id}-{self.span_id}-01'

    def __repr__(self) -> str:
        return f'SpanContext({self.traceparent()})'


def enabled() -> bool:
    return bool(os.environ.get(ENV_VAR))


def set_hop(name: str) -> None:
    """Name this process's hop ('server', 'worker', 'agent', ...); spans
    record it so per-hop latency is separable. Defaults to 'client'."""
    global _hop
    _hop = name


def get_hop() -> str:
    return _hop or os.environ.get('SKY_TPU_TRACE_HOP') or 'client'


def _new_id(nbytes: int) -> str:
    return os.urandom(nbytes).hex()


def parse_traceparent(value: Optional[str]) -> Optional[SpanContext]:
    """Parse a traceparent string; malformed input yields None (a bad
    header must never fail the request carrying it)."""
    if not value:
        return None
    m = _TRACEPARENT_RE.match(value.strip())
    if not m:
        return None
    return SpanContext(m.group(1), m.group(2))


def current() -> Optional[SpanContext]:
    """The active span context: contextvar first (same process), then
    the env-var handoff a parent process may have left."""
    ctx = _current.get()
    if ctx is None:
        ctx = parse_traceparent(os.environ.get(CTX_ENV_VAR))
    return ctx


def current_traceparent() -> Optional[str]:
    ctx = current()
    return ctx.traceparent() if ctx else None


@contextlib.contextmanager
def use_context(ctx: Optional[SpanContext]):
    """Run a block under an explicit parent context (cross-thread /
    cross-process handoff: the worker re-parents to the server's span,
    the agent's job runner to the submit span)."""
    token = _current.set(ctx)
    try:
        yield
    finally:
        _current.reset(token)


def context_from(traceparent: Optional[str]):
    return use_context(parse_traceparent(traceparent))


def bind(fn: Callable) -> Callable:
    """Capture the current context into a callable about to run on
    another thread (executors do not inherit contextvars)."""
    if not enabled():
        return fn
    ctx = current()

    @functools.wraps(fn)
    def inner(*a, **kw):
        with use_context(ctx):
            return fn(*a, **kw)

    return inner


def inject_headers(headers: Dict[str, str]) -> Dict[str, str]:
    """Add the traceparent header for an outbound hop. Mutates and
    returns ``headers``; skipped entirely when tracing is off."""
    if enabled():
        tp = current_traceparent()
        if tp:
            headers[HEADER] = tp
    return headers


def inject_payload(payload: Dict[str, Any]) -> Dict[str, Any]:
    """Stamp the context into a request payload (server → worker: the
    worker re-reads the persisted row, not our memory)."""
    if enabled():
        tp = current_traceparent()
        if tp:
            payload[PAYLOAD_KEY] = tp
    return payload


def child_env(env: Dict[str, str]) -> Dict[str, str]:
    """Stamp the context into a child process environment."""
    if enabled():
        tp = current_traceparent()
        if tp:
            env[CTX_ENV_VAR] = tp
    return env


def agent_trace_config() -> Dict[str, Any]:
    """Keys a provisioner merges into agent_config.json so tracing
    reaches REAL (remote) agent hosts, where the provisioner's env does
    not: `trace_enabled`, plus `trace_collector` when the operator set
    SKY_TPU_TRACE_AGENT_COLLECTOR (the API server URL as reachable
    from the cluster). Empty when tracing is off."""
    if not enabled():
        return {}
    cfg: Dict[str, Any] = {'trace_enabled': True}
    collector = os.environ.get(AGENT_COLLECTOR_ENV_VAR)
    if collector:
        cfg['trace_collector'] = collector
    return cfg


class _SpanHandle:
    """Yielded by ``span()`` so the body can attach attributes that are
    only known mid-span (e.g. the request_id minted inside)."""

    __slots__ = ('ctx', 'attrs')

    def __init__(self, ctx: SpanContext, attrs: Dict[str, Any]) -> None:
        self.ctx = ctx
        self.attrs = attrs

    def set_attr(self, key: str, value: Any) -> None:
        self.attrs[key] = value


@contextlib.contextmanager
def span(name: str, *, hop: Optional[str] = None, **attrs: Any):
    """Record one span around a block. No-op (yields None) when tracing
    is disabled."""
    if not enabled():
        yield None
        return
    parent = current()
    ctx = SpanContext(parent.trace_id if parent else _new_id(16),
                      _new_id(8))
    handle = _SpanHandle(ctx, dict(attrs))
    token = _current.set(ctx)
    t0 = time.time()
    status = 'ok'
    try:
        yield handle
    except BaseException as e:
        status = f'error:{type(e).__name__}'
        raise
    finally:
        _current.reset(token)
        record_span(
            name=name, trace_id=ctx.trace_id, span_id=ctx.span_id,
            parent_id=parent.span_id if parent else None,
            start=t0, dur_s=time.time() - t0, status=status,
            hop=hop or get_hop(), attrs=handle.attrs)


def traced(fn: Callable = None, *, name: Optional[str] = None,
           hop: Optional[str] = None) -> Callable:
    """Decorator form. Gated at decoration time (same zero-cost default
    as ``timeline.event``): with ``SKY_TPU_TRACE`` unset the original
    function is returned unchanged — no wrapper, no per-call check."""

    def wrap(f: Callable) -> Callable:
        if not enabled():
            return f
        label = name or f'{f.__module__}.{f.__qualname__}'

        @functools.wraps(f)
        def inner(*a, **kw):
            with span(label, hop=hop):
                return f(*a, **kw)

        return inner

    return wrap(fn) if fn is not None else wrap


def record_span(*, name: str, trace_id: str, span_id: str,
                parent_id: Optional[str], start: float, dur_s: float,
                status: str, hop: str,
                attrs: Optional[Dict[str, Any]] = None) -> None:
    global _flush_registered, _dropped
    s = {
        'trace_id': trace_id, 'span_id': span_id,
        'parent_id': parent_id, 'name': name, 'hop': hop,
        'start': start, 'dur_s': dur_s, 'status': status,
        'attrs': attrs or {},
    }
    with _buffer_lock:
        if len(_buffer) >= _MAX_BUFFER:
            _dropped += 1
            return
        _buffer.append(s)
        if not _flush_registered:
            atexit.register(flush)
            _flush_registered = True
    _ensure_shipper()


def _ensure_shipper() -> None:
    global _shipper_started
    if _shipper_started:
        return
    with _shipper_lock:
        if _shipper_started:
            return
        _shipper_started = True

        def loop() -> None:
            while True:
                time.sleep(_SHIP_INTERVAL_S)
                try:
                    flush()
                except Exception:  # noqa: BLE001 — fail-open
                    pass

        threading.Thread(target=loop, daemon=True,
                         name='trace-shipper').start()


def set_sink(sink: Optional[Callable[[List[Dict[str, Any]]], Any]]
             ) -> None:
    """Install a local sink (the API server: spans go straight into the
    store + metrics instead of over HTTP to itself)."""
    global _sink
    _sink = sink


def _resolve_collector() -> Optional[str]:
    url = (os.environ.get(COLLECTOR_ENV_VAR) or
           os.environ.get('SKY_TPU_API_SERVER'))
    if url:
        return url.rstrip('/')
    # Config-declared API endpoint (the SDK's own fallback chain).
    try:
        from skypilot_tpu import config as config_lib
        url = config_lib.get_nested(('api_server', 'endpoint'))
        if url:
            return url.rstrip('/')
    except Exception:  # noqa: BLE001 — config layer unavailable
        pass
    # Same host as a running API server? Its startup file names the URL.
    try:
        import json

        from skypilot_tpu.utils import common
        path = os.path.join(common.base_dir(), 'api_server.json')
        with open(path, encoding='utf-8') as f:
            return json.load(f)['url'].rstrip('/')
    except Exception:  # noqa: BLE001 — no server around: ship locally
        return None


def flush() -> int:
    """Ship buffered spans. Best-effort, fail-open: a collector POST
    failure falls back to the local store; a store failure drops. Never
    raises. Returns the number of spans handed off."""
    with _buffer_lock:
        if not _buffer:
            return 0
        spans, _buffer[:] = list(_buffer), []
    if _sink is not None:
        try:
            _sink(spans)
        except Exception:  # noqa: BLE001 — fail-open
            pass
        return len(spans)
    collector = _resolve_collector()
    if collector:
        try:
            import requests

            # Lazy import: retry.py imports this module at its top
            # level, so the dependency must only run at call time.
            from skypilot_tpu.utils import retry as retry_lib

            def _post() -> None:
                r = requests.post(f'{collector}/api/traces',
                                  json={'spans': spans}, timeout=3)
                r.raise_for_status()

            # Two quick tries, then fall back to the local store —
            # shipping is fail-open and must never stall the caller.
            retry_lib.Retrier(
                'trace.ship', max_attempts=2, base_delay_s=0.1,
                max_delay_s=0.5,
                transient=(requests.RequestException,)).call(_post)
            return len(spans)
        except Exception:  # noqa: BLE001 — fall through to local store
            pass
    try:
        from skypilot_tpu.observability import store as store_lib
        store_lib.ingest(spans)
    except Exception:  # noqa: BLE001 — fail-open
        pass
    return len(spans)


def _reset_for_tests() -> None:
    """Drop all module state (buffered spans, sink, hop)."""
    global _dropped, _sink, _hop
    with _buffer_lock:
        _buffer[:] = []
        _dropped = 0
    _sink = None
    _hop = None


def buffered() -> Tuple[int, int]:
    """(buffered, dropped) counts — introspection for tests/debugging."""
    with _buffer_lock:
        return len(_buffer), _dropped
