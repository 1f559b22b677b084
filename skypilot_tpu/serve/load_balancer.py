"""Serve load balancer: HTTP proxy over the ready replica set.

Counterpart of the reference's ``sky/serve/load_balancer.py``
(``SkyServeLoadBalancer`` :24, ``run_load_balancer`` :289). aiohttp on
both sides: an aiohttp server accepts user requests, an aiohttp client
session streams them to the selected replica. The ready-replica set is
refreshed from the serve state DB every second (the reference syncs it
from the controller over HTTP); request counts are flushed back to the DB
as the autoscaler's QPS signal.

Resilience (docs/robustness.md): a replica failure BEFORE the first
response byte is retried on the next ready replica — a dead replica
costs zero client-visible errors as long as one peer survives. Each
replica has a circuit breaker (utils/retry.CircuitBreaker): consecutive
pre-stream failures trip it OPEN so the selector stops offering the
corpse, and a half-open probe re-admits it when it recovers.

Mid-stream death IS retried for /generate token streams (resumable
generation, docs/robustness.md "Zero-downtime serving"): the LB tracks
the token ids of every COMPLETE jsonlines line it forwarded; when the
upstream dies before the done line, it re-issues the request to the
next replica with ``resume_from = delivered_tokens`` and splices the
continuation into the SAME client response. The replica prefills
prompt+delivered (a near-pure prefix-cache hit under cache_aware
routing) and emits only new tokens, so greedy output is bit-identical
to an unkilled run and the client never sees the failure. Only
non-resumable bodies keep the old rule (truncation = the error signal).
Overload is routed around, not amplified: a replica answering 429/503
is released (never a breaker failure) and the request tries the next
replica; per-request deadlines (utils/common.DEADLINE_HEADER) forward
the REMAINING budget on every retry leg.
"""
from __future__ import annotations

import asyncio
import collections
import contextlib
import hashlib
import json
import logging
import os
from typing import Callable, Dict, List, Optional, Set

import aiohttp
from aiohttp import web

from skypilot_tpu import exceptions
from skypilot_tpu.observability import integrity
from skypilot_tpu.observability import prometheus as prom_lib
from skypilot_tpu.observability import slo as slo_lib
from skypilot_tpu.observability import stepline as stepline_lib
from skypilot_tpu.observability import trace as trace_lib
from skypilot_tpu.serve import fleet_index as fleet_index_lib
from skypilot_tpu.serve import load_balancing_policies as lbp
from skypilot_tpu.serve import state as serve_state
from skypilot_tpu.utils import common
from skypilot_tpu.utils import prefix_hash
from skypilot_tpu.utils import failpoints
from skypilot_tpu.utils import retry as retry_lib
from skypilot_tpu.utils import vclock

logger = logging.getLogger(__name__)

SYNC_INTERVAL_S = 1.0
STATS_FLUSH_S = 2.0
# How long a parked (scale-to-zero wake) request waits for capacity
# before shedding — a full cold start is provision + weights + compile,
# so this is minutes, not the retry-loop's seconds.
WAKE_TIMEOUT_S = float(os.environ.get('SKY_TPU_LB_WAKE_TIMEOUT_S',
                                      '600'))


def _env_interval(name: str, default: float) -> float:
    """Fail-open float knob (the SKY_TPU_LB_HISTORY rule): a malformed
    value must never keep the LB from starting, and a non-positive
    interval would spin the maintenance loops — floor at 10ms."""
    try:
        v = float(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default
    return max(0.01, v)
# Fleet metrics history: samples retained per replica (one per sync
# tick — 120 at the 1 s default ≈ two minutes of signal), surfaced at
# /-/metrics/history and as windowed-rate gauges in /-/metrics. The
# signal shape the catalog autoscaler and the fleet digital twin
# consume (docs/observability.md "Flight recorder").
def _history_len() -> int:
    # Fail-open like every other recorder knob (store TTL, dump
    # interval): a malformed value must never keep the LB from
    # starting, and deque(maxlen=<1) would break the sync tick.
    try:
        n = int(os.environ.get('SKY_TPU_LB_HISTORY', '120'))
    except (TypeError, ValueError):
        return 120
    return max(1, n)


HISTORY_LEN = _history_len()
# Hop-by-hop headers never forwarded by proxies (RFC 9110 §7.6.1).
_HOP_HEADERS = frozenset((
    'connection', 'keep-alive', 'proxy-authenticate',
    'proxy-authorization', 'te', 'trailers', 'transfer-encoding',
    'upgrade', 'host', 'content-length'))


class _PreStreamFailure(Exception):
    """Replica failed before any response byte reached the client —
    safe to retry on another replica."""

    def __init__(self, cause: BaseException) -> None:
        super().__init__(str(cause))
        self.cause = cause


class _UpstreamDead(Exception):
    """A resumable /generate stream's upstream died (pre- OR
    mid-stream, it no longer matters): the handler re-issues the tail
    on the next replica with ``resume_from`` and splices it into the
    same client response. A breaker failure either way."""

    def __init__(self, cause: BaseException) -> None:
        super().__init__(str(cause))
        self.cause = cause


class _ClientGone(Exception):
    """The CLIENT side vanished while we were proxying (disconnect or
    reset on a write to it). Never the replica's fault: the breaker
    slot is released — not failed — on every leg, initial and resumed
    alike."""

    def __init__(self, cause: BaseException) -> None:
        super().__init__(str(cause))
        self.cause = cause


class _ReplicaSaturated(Exception):
    """The replica shed a /generate request (429 admission-full, or
    503 while draining) before any byte reached the client. Overload is
    not death: the breaker is released, the next replica is tried, and
    only when EVERY replica sheds does the client see the last 429/503
    (headers preserved, Retry-After guaranteed). Scoped to /generate —
    arbitrary proxied endpoints keep the old rule (a 5xx feeds the
    breaker), so a replica whose app 503s every request still trips
    out of rotation."""

    def __init__(self, status: int, body: bytes,
                 headers: Dict[str, str]) -> None:
        super().__init__(f'replica shed with {status}')
        self.status = status
        self.body = body
        self.headers = {k: v for k, v in headers.items()
                        if k.lower() not in _HOP_HEADERS}
        self.headers.setdefault('Retry-After', '1')


class _QuarantineCut(Exception):
    """The replica serving this stream leg was QUARANTINED (golden
    probe mismatch / corrupt self-report) while tokens were in flight:
    the leg is severed on the next line boundary and the stream
    resumes on a healthy replica — delivered tokens were CRC-verified
    up to the cut, so the spliced stream stays bit-identical. Breaker
    is RELEASED, never failed: quarantine is the integrity plane's
    verdict, not a liveness failure."""


class _StreamSplice:
    """Cross-attempt state of one resumable /generate token stream.

    The client sees exactly one response; legs against successive
    replicas append to it. ``delivered`` holds the token ids of every
    COMPLETE jsonlines line forwarded so far — the dedupe rule at the
    resume boundary: a line cut mid-flight by the failure is discarded
    (never counted, never forwarded), so the resume leg — which emits
    only tokens after ``resume_from`` — regenerates exactly the
    undelivered tail. Nothing is duplicated, nothing is lost, and for
    greedy decoding the spliced stream is bit-identical to an unkilled
    run."""

    def __init__(self, payload: Dict[str, object], orig_body: bytes,
                 tenant: Optional[str] = None) -> None:
        self.payload = payload
        self.orig_body = orig_body
        self.tenant = tenant
        try:
            self.client_resume = [
                int(t) for t in (payload.get('resume_from') or ())]
        except (TypeError, ValueError):
            self.client_resume = []   # the replica will 400 it
        self.resp: Optional[web.StreamResponse] = None
        self.delivered: List[int] = []
        self.buf = b''
        self.done = False
        self.resumes = 0
        # TTFT/ITL bookkeeping carried across legs.
        self.first = True
        self.t_prev: Optional[float] = None
        self.pending_gap: Optional[float] = None

    def body(self) -> bytes:
        if not self.resumes:
            return self.orig_body
        p = dict(self.payload)
        p['resume_from'] = self.client_resume + self.delivered
        return json.dumps(p).encode()


def _mean_gauge(stats: 'Dict[str, dict]', key: str):
    """Mean of a per-replica gauge over the replicas reporting it
    (None when nobody does) — fleet decode-efficiency rollup."""
    vals = [row[key] for row in stats.values()
            if isinstance(row, dict) and row.get(key) is not None]
    return round(sum(vals) / len(vals), 4) if vals else None


class LoadBalancer:
    # Concurrency contract (SKY-LOCK, docs/static-analysis.md):
    # 'event-loop' = single-threaded asyncio state. Counters and
    # gauges are only coherent because every touch happens on the
    # loop — from `async def` bodies, or sync methods annotated
    # '# holds: event-loop' whose callers are all coroutines. A
    # thread (or executor callback) reaching in unsynchronized would
    # tear the read-modify-writes.
    _GUARDED_BY = {
        '_pending_requests': 'event-loop',
        '_inflight': 'event-loop',
        '_ttfts': 'event-loop',
        '_itls': 'event-loop',
        '_requests_total': 'event-loop',
        '_requests_failed': 'event-loop',
        '_requests_no_replica': 'event-loop',
        '_requests_retried': 'event-loop',
        '_requests_resumed': 'event-loop',
        '_requests_shed': 'event-loop',
        '_draining_urls': 'event-loop',
        '_tenants': 'event-loop',
        '_replica_queue_depth': 'event-loop',
        '_replica_decode_stats': 'event-loop',
        '_replica_history': 'event-loop',
        '_sync_tick': 'event-loop',
        '_history_tick': 'event-loop',
        '_breaker_open_seen': 'event-loop',
        '_breaker_pending': 'event-loop',
        '_breaker_dump_at': 'event-loop',
        'slo': 'event-loop',
        '_slo_cfg': 'event-loop',
        '_slo_reload_tick': 'event-loop',
        '_slo_pending': 'event-loop',
        '_slo_dump_at': 'event-loop',
        '_wake_cfg': 'event-loop',
        '_wake_reload_tick': 'event-loop',
        '_parked': 'event-loop',
        '_parked_total': 'event-loop',
        '_wake_started_t': 'event-loop',
        '_cold_starts': 'event-loop',
        '_cold_starts_total': 'event-loop',
        '_cost_gauges': 'event-loop',
        # Golden-probe canary plane (docs/robustness.md "Data
        # integrity"): all touched from the sync tick + probe tasks,
        # both on the loop.
        '_probe_inflight': 'event-loop',
        '_probe_last': 'event-loop',
        '_probe_failures': 'event-loop',
        '_replicas_quarantined': 'event-loop',
        '_quarantined_urls': 'event-loop',
        '_replica_ids': 'event-loop',
        # Fleet prefix tier (docs/serving.md "Disaggregated
        # prefill/decode"): the index folds on the sync tick, the
        # selector reads it per request — both on the loop.
        'fleet_index': 'event-loop',
        '_fleet_lookups': 'event-loop',
        '_fleet_hits': 'event-loop',
        '_pending_donor': 'event-loop',
        # Incident-replay evidence rings (docs/simulation.md):
        # appended from handle() and the sync tick, snapshotted into
        # fleet dumps — all on the loop.
        '_request_events': 'event-loop',
        '_fleet_events': 'event-loop',
        '_prev_ready': 'event-loop',
        '_recoveries_seen': 'event-loop',
        '_quarantine_pending': 'event-loop',
        '_quarantine_dump_at': 'event-loop',
    }

    # Per-request chaining cap: at most this many page blocks of the
    # prompt are hashed for the fleet lookup (the replica-side export
    # cap bounds what a donor would ship anyway).
    _CHAIN_LIMIT = 64

    def __init__(self, service_name: str, policy_name: str, *,
                 clock: Optional[vclock.Clock] = None,
                 probe_fixture=None, probe_fingerprint=None,
                 probe_interval_s: Optional[float] = None,
                 fleet_routing: Optional[bool] = None) -> None:
        self.service_name = service_name
        self.policy = lbp.make(policy_name)
        self._policy_name = policy_name
        # Fleet prefix tier (docs/serving.md "Disaggregated prefill/
        # decode"): on by default; SKY_TPU_LB_FLEET_ROUTING=0 (or the
        # ctor arg — the twin's scenario switch) pins the legacy
        # owner-only consistent-hash path. The tier only ever acts on
        # cache_aware + token prompts + an armed index, so "on" is
        # inert everywhere else.
        if fleet_routing is None:
            fleet_routing = os.environ.get(
                'SKY_TPU_LB_FLEET_ROUTING', '1') != '0'
        self.fleet_routing = bool(fleet_routing)
        self.fleet_index = fleet_index_lib.FleetPrefixIndex()
        self._fleet_lookups = 0
        self._fleet_hits = 0
        # Donor handoff between _select and the attempt loop (reset at
        # every selection; consumed before the next await).
        self._pending_donor: Optional[str] = None
        # Clock seam (utils/vclock): wall reads (history stamps, dump
        # rate limits) and interval reads (TTFT/ITL stopwatches,
        # deadlines, breaker cooldowns) both route through here so the
        # digital twin replays the whole request path in virtual time.
        self._clock = clock or vclock.get()
        # Maintenance cadences, env-tunable fail-open (a fleet-scale
        # twin or a 1000-replica deployment wants a coarser sync tick
        # than the 1s default; docs/robustness.md "Digital twin").
        self.sync_interval_s = _env_interval(
            'SKY_TPU_LB_SYNC_INTERVAL_S', SYNC_INTERVAL_S)
        self.stats_flush_s = _env_interval(
            'SKY_TPU_LB_STATS_FLUSH_S', STATS_FLUSH_S)
        self._session: Optional[aiohttp.ClientSession] = None
        self._pending_requests = 0
        self._inflight = 0
        self._running = True
        # run()'s idle wait parks on this event instead of a sleep
        # poll; stop() sets it for prompt teardown.
        self._stop_event: Optional[asyncio.Event] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        # TTFT per proxied request: arrival -> first response byte from
        # the replica (the BASELINE.md north-star serving metric; for a
        # streaming LLM endpoint this is time-to-first-token as the
        # client experiences it through the LB).
        self._ttfts: collections.deque = collections.deque(maxlen=4096)
        # Inter-chunk gaps on proxied streams (for /generate streaming
        # this tracks inter-token latency as the client experiences it
        # — the metric the engine's overlapped decode pipeline moves).
        self._itls: collections.deque = collections.deque(maxlen=8192)
        self._requests_total = 0
        self._requests_failed = 0
        # "No capacity" is a different dashboard line than "replica
        # died": 503s are counted here, never in requests_failed.
        self._requests_no_replica = 0
        # Pre-stream failovers onto another replica (each one is a
        # client error that did NOT happen).
        self._requests_retried = 0
        # Mid-stream failovers: a /generate stream whose upstream died
        # was resumed on another replica and spliced into the same
        # client response (counted per resume leg).
        self._requests_resumed = 0
        # Requests shed to the CLIENT with 429/503 after every replica
        # refused (admission control end state).
        self._requests_shed = 0
        # Replicas currently draining (graceful scale-down/preemption
        # handoff): out of the ready set, surfaced in /-/metrics.
        self._draining_urls: List[str] = []
        # Per-tenant client-side view (X-SkyTpu-Tenant on /generate):
        # request/shed counts + a TTFT window each, surfaced under
        # /-/metrics 'tenants' so fairness is observable at the edge.
        self._tenants: Dict[str, dict] = {}
        # url -> engine num_waiting, refreshed by the sync loop from
        # each ready replica's /metrics: the scheduler-backlog gauge
        # the QueueLengthAutoscaler scales on (LB in-flight alone
        # misses queued-but-unserved work inside the engines).
        self._replica_queue_depth: Dict[str, int] = {}
        # url -> decode-efficiency gauges from the same /metrics fetch
        # (tokens_per_step, accepted_len_mean, spec_accept_rate) —
        # how many tokens each replica lands per engine step under
        # speculative decoding.
        self._replica_decode_stats: Dict[str, dict] = {}
        # url -> bounded history ring of those per-tick samples (plus
        # the raw decode/prefix counters, so windowed RATES derive
        # from deltas): the fleet tier of the flight recorder.
        # Pruned with the ready set, like the breaker.
        self._replica_history: Dict[str, collections.deque] = {}
        # Sync-tick counter + per-url tick of the last successful
        # /metrics sample: the staleness signal for the windowed
        # gauges. Ticks advance even when every fetch fails, so a
        # fleet whose ONLY replica hangs still goes stale (a
        # newest-ring-relative guard alone cannot see that — the
        # frozen ring is its own freshest).
        self._sync_tick = 0
        self._history_tick: Dict[str, int] = {}
        # Breaker states seen OPEN last tick — the edge detector for
        # the breaker_open anomaly dump (fleet history → span store)
        # — and the last dump's wall time: a hard-down replica
        # re-edges open every cooldown cycle (open → half-open →
        # failed probe → open), and without the same per-trigger rate
        # limit the engine triggers have, a flapping replica would
        # write a full fleet dump every ~10 s indefinitely.
        self._breaker_open_seen: Set[str] = set()
        # Edges that arrived rate-limited: still owed a fleet dump
        # once the interval passes, even if the breaker has closed
        # again by then (the edge is the incident, not the state).
        self._breaker_pending: Set[str] = set()
        self._breaker_dump_at = 0.0
        # SLO burn-rate evaluator (docs/observability.md "SLOs and
        # alerting"): objectives load from the service spec's `slo:`
        # section (or SKY_TPU_LB_SLO) on the first sync tick and
        # re-read every _SLO_RELOAD_TICKS so a `serve update` that
        # adds/changes objectives arms the running LB (the evaluator
        # rebuilds — burn history resets — only when the normalized
        # config actually changed). None = no objectives, inert.
        self.slo: Optional[slo_lib.SloEvaluator] = None
        self._slo_cfg: Optional[list] = None
        self._slo_reload_tick = 0
        # Page-tier firing edges owed a fleet dump (rate-limited like
        # breaker edges — deferred, never dropped) + the observation
        # seam the digital twin hangs its decision log on (called with
        # each alert transition record; never touches LB state).
        self._slo_pending: Set[str] = set()
        self._slo_dump_at = 0.0
        self.slo_transition_hook: Optional[Callable] = None
        # Scale-to-zero parking (docs/cost.md "Scale to zero"): when
        # the service declares `min_replicas: 0` + `wake_on_request`,
        # a request arriving at an empty ready set parks in a bounded
        # queue instead of bouncing off the 503 branch — the parked
        # in-flight count IS the queue signal the autoscaler wakes the
        # fleet on. Config piggybacks the sync tick's spec reload
        # (same cadence as the SLO reload); None = parking off.
        self._wake_cfg: Optional[dict] = None
        self._wake_reload_tick = 0
        self._parked: List[dict] = []
        self._parked_total = 0
        # Cold-start stopwatch: armed when the first request parks
        # against an empty fleet, sampled when the ready set comes
        # back — the client-experienced wake latency (provision +
        # weights + compile + first readiness).
        self._wake_started_t: Optional[float] = None
        self._cold_starts: collections.deque = collections.deque(
            maxlen=256)
        self._cold_starts_total = 0
        # Fleet economics gauges flushed by the controller
        # (state.get_cost_gauges), refreshed on the sync tick.
        self._cost_gauges: Optional[Dict[str, float]] = None
        # Incident-replay evidence rings (docs/simulation.md): one
        # SCRUBBED record per /generate arrival (lengths + a one-way
        # prefix-cohort hash — never token ids, so an exported
        # incident carries no prompt content) and one record per
        # fleet event (replica joins/losses, breaker edges,
        # quarantines, SLO transitions, controller recoveries). Both
        # snapshot into every fleet dump; the monotonic Ring totals
        # make wraparound truncation observable at export.
        self._request_events = stepline_lib.Ring(HISTORY_LEN * 4)
        self._fleet_events = stepline_lib.Ring(HISTORY_LEN * 2)
        # Ready-set of the previous sync tick — the edge detector for
        # replica_ready/replica_lost fleet events. None until the
        # first tick: a bootstrap (or crash-restarted) LB must not
        # record the whole fleet as "joining".
        self._prev_ready: Optional[Set[str]] = None
        # Controller crash watch: recoveries_total from the service
        # row (PR 14 journal), sampled on the spec-reload cadence — a
        # delta is a controller crash-recovery inside the incident
        # window.
        self._recoveries_seen: Optional[int] = None
        # Quarantine edges owed a fleet dump (deferred, never
        # dropped — the breaker-edge rate-limit rule).
        self._quarantine_pending: Set[str] = set()
        self._quarantine_dump_at = 0.0
        self.breaker = retry_lib.CircuitBreaker(
            failure_threshold=int(os.environ.get(
                'SKY_TPU_LB_BREAKER_THRESHOLD', '3')),
            cooldown_s=float(os.environ.get(
                'SKY_TPU_LB_BREAKER_COOLDOWN_S', '10')),
            clock=self._clock.monotonic)
        # Golden-probe canaries (docs/robustness.md "Data integrity"):
        # armed only when a fixture is configured — ctor args win (the
        # digital twin), else SKY_TPU_LB_PROBE_MODEL +
        # SKY_TPU_LB_PROBE_FINGERPRINT + SKY_TPU_LB_PROBE_INTERVAL_S.
        # Arming VALIDATES the fixture against the serving oracle's
        # fingerprint and raises StaleGoldenError on mismatch — loud
        # at startup, because armed-anyway the stale golden reads as a
        # fleet-wide quarantine storm. Unarmed = the whole plane is
        # inert (zero new syscalls, zero log lines).
        self._probe_fixture: Optional[integrity.GoldenFixture] = None
        self.probe_interval_s: Optional[float] = None
        self._probe_inflight: Set[str] = set()
        self._probe_last: Dict[str, float] = {}
        self._probe_failures = 0
        self._replicas_quarantined = 0
        # Sticky across QUARANTINED → DRAINING (the DB row leaves the
        # quarantined status the moment the drain starts, but the
        # mid-stream cut + _select exclusion must hold until the
        # replica is actually gone); repopulated from the DB each sync
        # tick, so a crash-restarted LB rebuilds it in bootstrap.
        self._quarantined_urls: Set[str] = set()
        self._replica_ids: Dict[str, int] = {}
        # Twin observation seam: called with (url, replica_id, reason)
        # whenever THIS LB commits a quarantine; never touches state.
        self.quarantine_hook: Optional[Callable] = None
        env_model = os.environ.get('SKY_TPU_LB_PROBE_MODEL')
        if probe_fixture is None and env_model:
            probe_fixture = integrity.load_fixture(env_model)
            probe_fingerprint = os.environ.get(
                'SKY_TPU_LB_PROBE_FINGERPRINT')
            probe_interval_s = _env_interval(
                'SKY_TPU_LB_PROBE_INTERVAL_S', 15.0)
        if probe_fixture is not None:
            if probe_fingerprint is not None:
                integrity.check_fixture(probe_fixture,
                                        probe_fingerprint)
            self._probe_fixture = probe_fixture
            self.probe_interval_s = float(probe_interval_s
                                          if probe_interval_s
                                          else 15.0)

    # -- background sync ---------------------------------------------------
    async def _offload(self, fn: Callable, *args):
        """Run blocking state-DB / span-store work off the event loop.
        Seam: the digital twin overrides this to run inline — its
        sqlite lives on the sim thread and determinism forbids real
        thread hops."""
        return await asyncio.to_thread(fn, *args)

    async def _sync_loop(self) -> None:
        while self._running:
            await self._sync_once()
            await asyncio.sleep(self.sync_interval_s)

    async def _sync_once(self) -> None:
        """One replica-set sync tick (factored out of the loop so the
        digital twin can drive ticks at virtual-time cadence)."""
        # Chaos seam: an injected process crash of the LB
        # (docs/robustness.md "Crash safety") — the error escapes the
        # fail-open try below on purpose, so the sync plane dies the
        # way a killed process would; recovery is a NEW LoadBalancer
        # calling bootstrap_from_state(), not this loop healing.
        await failpoints.hit_async('serve.lb.crash')
        # The tick advances OUTSIDE the try: the staleness guard
        # on the windowed gauges relies on it outrunning frozen
        # rings even when the sync body itself fails (state-DB
        # hiccup) — inside, a failing body would freeze counter
        # and rings together and the phantom rate would survive.
        self._sync_tick += 1
        try:
            info = await self._offload(
                serve_state.ready_replica_info, self.service_name)
            self.policy.set_replica_info(info)
            self.policy.set_ready_replicas(list(info))
            # Replicas that left the ready set drop their breaker
            # state; a returning URL starts closed.
            self.breaker.prune(info)
            self._draining_urls = await self._offload(
                serve_state.draining_replica_urls, self.service_name)
            # Ready-set edges → fleet events (incident-replay
            # evidence): losses use the PREVIOUS tick's id map — the
            # departed url is gone from `info`. The first tick only
            # sets the baseline (a bootstrap rebuild is not an
            # incident).
            ready_now = set(info)
            if self._prev_ready is not None:
                for url in sorted(ready_now - self._prev_ready):
                    self._fleet_event(
                        'replica_ready', replica=url,
                        replica_id=info[url]['replica_id'])
                for url in sorted(self._prev_ready - ready_now):
                    self._fleet_event(
                        'replica_lost', replica=url,
                        replica_id=self._replica_ids.get(url))
            self._prev_ready = ready_now
            self._replica_ids = {
                url: row['replica_id'] for url, row in info.items()}
            # Quarantine exclusion set: the DB rows are authoritative,
            # but a quarantined replica moves QUARANTINED → DRAINING
            # the moment the replica manager picks it up — keep a url
            # sticky while it is still ready/draining/quarantined and
            # drop it when the replica is gone (replaced). A restarted
            # LB rebuilds the set here (bootstrap_from_state runs one
            # sync tick).
            db_q = set(await self._offload(
                serve_state.quarantined_replica_urls,
                self.service_name))
            self._quarantined_urls = (
                (self._quarantined_urls
                 & (set(info) | set(self._draining_urls) | db_q))
                | db_q)
            if hasattr(self.policy, 'set_target_qps_per_accelerator'):
                # Instance-aware policy: refresh the per-accelerator
                # QPS map from the (possibly updated) service spec.
                record = await self._offload(
                    serve_state.get_service, self.service_name)
                if record is not None:
                    tq = ((record['spec'].get('replica_policy') or {})
                          .get('target_qps_per_replica'))
                    if isinstance(tq, dict):
                        self.policy.set_target_qps_per_accelerator(tq)
            rows = await self._fetch_all_metrics(
                list(self.policy.ready_urls))
            # Fleet prefix tier: the radix summary and the replica's
            # role ride the same fetch — fold them into the index and
            # POP them so the history rings stay flat scalar rows.
            for url, _, eff in rows:
                self.fleet_index.set_role(url, eff.pop('role', None))
                snap = eff.pop('kv_prefix_index', None)
                if snap is not None and self.fleet_routing:
                    self.fleet_index.apply(url, snap)
            self.fleet_index.prune(info)
            self._replica_queue_depth = {
                url: depth for url, depth, _ in rows}
            self._replica_decode_stats = {
                url: eff for url, _, eff in rows}
            # Fleet history tier: one sample per replica per tick,
            # bounded per replica; replicas leaving the ready set
            # drop their ring (same lifetime rule as the breaker).
            now = self._clock.time()
            for url, depth, eff in rows:
                ring = self._replica_history.get(url)
                if ring is None:
                    ring = self._replica_history[url] = (
                        collections.deque(maxlen=HISTORY_LEN))
                ring.append({'t': now, 'queue_depth': depth,
                             **eff})
                self._history_tick[url] = self._sync_tick
            for url in list(self._replica_history):
                if url not in info:
                    del self._replica_history[url]
                    self._history_tick.pop(url, None)
            await self._slo_tick(now)
            await self._wake_tick()
            self._probe_round(now)
            self._cost_gauges = await self._offload(
                serve_state.get_cost_gauges, self.service_name)
            await self._dump_breaker_edges()
            await self._dump_quarantine_edges(now)
        except Exception:  # noqa: BLE001 — keep serving on DB hiccup
            logger.warning('replica sync failed', exc_info=True)

    async def _fetch_all_metrics(self, urls: List[str]) -> List[tuple]:
        """Engine queue-depth gauge: each ready replica's /metrics
        num_waiting (the scheduler backlog), fetched CONCURRENTLY so
        one slow/blackholed replica costs the tick max(timeouts), not
        their sum — a warming/dead replica simply has no gauge this
        tick. Seam: the twin overrides this to read its modeled
        replicas directly."""
        if self._session is None or not urls:
            return []
        fetched = await asyncio.gather(
            *(self._fetch_replica_metrics(u) for u in urls))
        return [row for row in fetched if row is not None]

    async def _fetch_replica_metrics(self, url: str) -> Optional[tuple]:
        try:
            # `prefix_gen` asks the replica to delta-encode its radix
            # summary against our mirror's generation — steady-state
            # ticks carry a tiny journal, not the full hash list.
            qs = (f'?prefix_gen={self.fleet_index.last_gen(url)}'
                  if self.fleet_routing else '')
            async with self._session.get(
                    url.rstrip('/') + '/metrics' + qs,
                    timeout=aiohttp.ClientTimeout(total=2)) as r:
                if r.status == 200:
                    m = await r.json()
                    # Decode-efficiency gauges ride the same fetch:
                    # tokens/step (>1 under speculative decoding) and
                    # the spec acceptance stats dashboards watch.
                    eff = {
                        k: m.get(k) for k in (
                            'tokens_per_step',
                            'accepted_len_mean',
                            'spec_accept_rate',
                            # Raw counters ride along so the history
                            # tier can derive windowed RATES from
                            # deltas.
                            'decode_tokens',
                            'prefix_hits',
                            'prefix_misses',
                            'prefix_hit_rate',
                            # KV streaming counters (docs/serving.md
                            # "Disaggregated prefill/decode") for the
                            # fleet rollup.
                            'kv_transfers_total',
                            'kv_transfer_bytes',
                            'kv_transfer_failures',
                            'kv_transfer_p99_s')
                        if m.get(k) is not None}
                    # Non-scalar riders for the fleet prefix index —
                    # the sync tick POPS these before the history
                    # append.
                    if m.get('role') is not None:
                        eff['role'] = m['role']
                    if m.get('kv_prefix_index') is not None:
                        eff['kv_prefix_index'] = m['kv_prefix_index']
                    return url, int(m.get('num_waiting') or 0), eff
        except (aiohttp.ClientError, asyncio.TimeoutError, ValueError,
                TypeError, OSError):
            pass
        return None

    # -- flight-recorder evidence rings (docs/simulation.md) ---------------
    def _fleet_event(self, kind: str, **fields) -> None:
        """Append one control-plane event to the fleet-event ring —
        the fault-timeline half of an exported incident (the request
        ring is the arrival half). Timestamps go through the clock
        seam so twin-grown incidents carry virtual time."""
        self._fleet_events.append(
            {'t': round(self._clock.time(), 6), 'kind': kind,
             **fields})

    def _fleet_dump_spans(self, trigger: str, detail: dict) -> list:
        """One fleet dump, incident-export grade: the per-replica
        metrics history PLUS both evidence rings and the LB config the
        converter needs to rebuild a Scenario (policy, cadences, SLO
        objectives). Every anomaly dump goes through here so
        `sky-tpu incident export` works on any of them."""
        detail = dict(detail)
        detail.update({
            'lb_policy': self._policy_name,
            'sync_interval_s': self.sync_interval_s,
            'probe_interval_s': self.probe_interval_s,
            'slo_cfg': self._slo_cfg or [],
        })
        return stepline_lib.fleet_history_spans(
            trigger, detail,
            {u: list(r) for u, r in self._replica_history.items()},
            request_events=self._request_events.snapshot(),
            request_events_total=self._request_events.total,
            fleet_events=self._fleet_events.snapshot(),
            fleet_events_total=self._fleet_events.total)

    def _note_request_event(self, payload: Dict[str, object],
                            tenant: Optional[str],
                            t_deadline: Optional[float],
                            t_arrival: float) -> Dict[str, object]:
        """Record one /generate arrival into the request ring,
        SCRUBBED at capture time: lengths and a one-way prefix-cohort
        hash, never token ids or text — an exported incident carries
        no prompt content by construction, not by a later filter
        step. Returns the (mutable) ring record so the terminal paths
        can fill in the outcome; the dump renderer copies attrs at
        dump time, so a still-in-flight request exports with
        ``outcome: null``."""
        toks = payload.get('tokens')
        if isinstance(toks, list) and toks:
            prompt_tokens = len(toks)
            # Same cohort semantics as sim.tracefmt.cohort_key
            # (inlined: serve/ must not import sim/ — the twin
            # imports serve/). The only contract is "same leading
            # block ⇒ same cohort", which materialization relies on.
            try:
                head = json.dumps(
                    [int(t) for t in toks[:16]]).encode()
                cohort = hashlib.blake2s(
                    head, digest_size=6).hexdigest()
            except (TypeError, ValueError):
                cohort = None
        else:
            text = payload.get('prompt')
            prompt_tokens = (max(1, len(text) // 4)
                             if isinstance(text, str) else 1)
            cohort = None
        try:
            max_new = int(payload.get('max_new_tokens') or 0) or None
        except (TypeError, ValueError):
            max_new = None
        rec: Dict[str, object] = {
            't': round(self._clock.time(), 6),
            'tenant': tenant,
            'prompt_tokens': prompt_tokens,
            'max_new_tokens': max_new,
            'cohort': cohort,
            'stream': bool(payload.get('stream')),
            'deadline_s': (round(t_deadline - t_arrival, 6)
                           if t_deadline is not None else None),
            'outcome': None,
            'output_tokens': None,
            'resumes': 0,
        }
        self._request_events.append(rec)
        return rec

    @staticmethod
    def _finish_event(rec: Optional[Dict[str, object]], outcome: str,
                      splice=None) -> None:
        """Stamp a request ring record's terminal outcome (first
        writer wins — the splice-exhausted path can race the deadline
        check)."""
        if rec is None or rec.get('outcome') is not None:
            return
        rec['outcome'] = outcome
        if splice is not None:
            rec['output_tokens'] = len(splice.delivered)
            rec['resumes'] = splice.resumes

    async def _dump_breaker_edges(self) -> None:
        """breaker_open anomaly: on a closed→open EDGE, snapshot the
        whole fleet metrics history into the span store (the black
        box for "why did that replica trip") — sqlite I/O off the
        event loop. Called once per sync tick."""
        # Anything not CLOSED counts as "still open" for the edge
        # detector: a hard-down replica cycles open → half-open →
        # failed probe → open every cooldown, and keying on 'open'
        # alone would re-arm the edge each cycle — an identical fleet
        # dump per rate-limit interval, forever, until the repeated
        # dumps GC ordinary request traces out of the span store.
        open_now = {u for u, s in self.breaker.snapshot().items()
                    if s != retry_lib.STATE_CLOSED}
        # (Wall reads below go through the clock seam so the twin's
        # rate-limit arithmetic is deterministic.)
        # A breaker that closed re-arms its edge; open ones we have
        # already dumped stay consumed. Pending edges (rate-limited
        # earlier) stay owed even if the breaker closed meanwhile —
        # the edge is the incident, and the ring still holds ~2 min
        # of the evidence.
        self._breaker_open_seen &= open_now
        new_open = ((open_now - self._breaker_open_seen)
                    | self._breaker_pending)
        if not new_open:
            return
        # Ring entries are written per EDGE, before the dump rate
        # limit: a deferred dump must still carry the true trip time,
        # not the time the rate limiter finally let it through.
        for url in sorted((open_now - self._breaker_open_seen)
                          - self._breaker_pending):
            self._fleet_event('breaker_open', replica=url,
                              replica_id=self._replica_ids.get(url))
        now = self._clock.time()
        min_s = stepline_lib.dump_interval_s()
        if min_s > 0 and now - self._breaker_dump_at < min_s:
            # Deferred, not dropped: a second replica tripping inside
            # the interval dumps on a later tick (unlike engine
            # triggers, a breaker edge is one-shot — dropping it
            # would lose the incident).
            self._breaker_pending = new_open
            return
        self._breaker_dump_at = now
        self._breaker_pending = set()
        self._breaker_open_seen |= new_open & open_now
        spans = self._fleet_dump_spans(
            'breaker_open', {'replicas_open': sorted(new_open)})
        await self._offload(stepline_lib.write_dump_sync, spans)

    # -- golden-probe canaries (docs/robustness.md "Data integrity") -------
    def _spawn_task(self, coro):  # holds: event-loop
        """Fire-and-forget task seam: the digital twin overrides this
        with its kernel's spawn so probes run in virtual time (the
        trampoline rejects foreign awaitables)."""
        return asyncio.ensure_future(coro)

    def _probe_round(self, now: float) -> None:  # holds: event-loop
        """Riding the sync tick: start a golden probe against every
        READY replica that is due (per-url interval) and not already
        being probed (≤1 in flight per replica — probe cost is bounded
        by construction, not by luck). Quarantined/draining urls are
        skipped: their verdict is already in."""
        if self._probe_fixture is None:
            return
        for url in sorted(self.policy.ready_urls):
            if (url in self._probe_inflight
                    or url in self._quarantined_urls):
                continue
            last = self._probe_last.get(url)
            if last is not None and now - last < self.probe_interval_s:
                continue
            self._probe_last[url] = now
            self._probe_inflight.add(url)
            self._spawn_task(self._probe_one(url))

    async def _probe_one(self, url: str) -> None:
        """One golden probe: replay the fixture prompt through the
        replica's NORMAL /generate path and compare the delivered
        token ids' CRC against the golden. Three verdicts:
        ``corrupt`` (the replica self-reported its sentinel tripped)
        and a CRC mismatch both QUARANTINE; a transport failure only
        counts ``probe_failures_total`` — integrity, never
        availability (a slow or momentarily unreachable replica is the
        breaker/brownout planes' business; only wrong BYTES quarantine
        — slow is not corrupt)."""
        fixture = self._probe_fixture
        try:
            status, data = await self._probe_transport(
                url, fixture.payload())
            if status == 'corrupt':
                await self._quarantine(url, 'sentinel')
                return
            if status != 'ok':
                self._probe_failures += 1
                return
            crc = integrity.token_crc(data)
            try:
                # Chaos seam: corrupt THIS compare (drives the
                # quarantine machinery without poisoning a replica).
                await failpoints.hit_async('serve.lb.probe_corrupt')
            except failpoints.FailpointError:
                crc = ~crc
            if crc != fixture.token_crc:
                await self._quarantine(url, 'probe_mismatch')
        except asyncio.CancelledError:
            raise  # LB shutdown — never a probe failure
        except Exception:  # noqa: BLE001 — a probe bug must not kill sync
            logger.warning('golden probe against %s errored', url,
                           exc_info=True)
            self._probe_failures += 1
        finally:
            self._probe_inflight.discard(url)

    async def _probe_transport(self, url: str, payload: dict):
        """Issue one probe request; returns ``('ok', token_ids)``,
        ``('corrupt', detail)`` when the replica sheds with the
        quarantined marker (its own sentinel tripped), or
        ``('error', detail)`` on any transport/shed/5xx outcome.
        Probes ride the PROBE_TENANT header and never touch the
        tenant ledgers, TTFT/ITL windows, or SLO ingestion — they
        bypass handle() entirely. Seam: the twin overrides this to
        drive its modeled replicas."""
        if self._session is None:
            return 'error', 'no session'
        try:
            async with self._session.post(
                    url.rstrip('/') + '/generate', json=payload,
                    headers={common.TENANT_HEADER:
                             integrity.PROBE_TENANT},
                    timeout=aiohttp.ClientTimeout(total=30)) as r:
                if r.status == 503:
                    try:
                        body = json.loads(await r.read() or b'{}')
                    except ValueError:
                        body = {}
                    if isinstance(body, dict) and body.get(
                            'quarantined'):
                        return 'corrupt', body.get('error') or ''
                    return 'error', f'shed {r.status}'
                if r.status != 200:
                    return 'error', f'status {r.status}'
                tokens: List[int] = []
                async for line in r.content:
                    if not line.strip():
                        continue
                    try:
                        obj = json.loads(line)
                    except ValueError:
                        return 'error', 'bad stream line'
                    if not isinstance(obj, dict):
                        return 'error', 'bad stream line'
                    if obj.get('error'):
                        return 'error', obj['error']
                    toks = obj.get('tokens')
                    if isinstance(toks, list):
                        tokens.extend(int(t) for t in toks)
                    if obj.get('done'):
                        return 'ok', tokens
                return 'error', 'stream ended without done'
        except (aiohttp.ClientError, asyncio.TimeoutError,
                OSError) as e:
            return 'error', f'{type(e).__name__}: {e}'

    async def _quarantine(self, url: str, reason: str) -> None:
        """Commit the quarantine: status + intent in ONE state-DB
        transaction (PR 14 crash machinery — a controller killed
        mid-quarantine reconciles to the same replace), then pull the
        url from routing immediately (the sync tick would catch it a
        tick later; in-flight streams cut at the next line boundary
        and resume elsewhere). The guarded UPDATE returns False when
        the replica already left READY/NOT_READY — two probes racing
        one bad replica count ONE quarantine."""
        rid = self._replica_ids.get(url)
        if rid is None:
            return
        did = await self._offload(
            serve_state.quarantine_replica, self.service_name, rid,
            reason)
        if not did:
            return
        self._replicas_quarantined += 1
        self._quarantined_urls.add(url)
        self._fleet_event('quarantine', replica=url, replica_id=rid,
                          reason=reason)
        self._quarantine_pending.add(url)
        logger.warning(
            'replica %d (%s) QUARANTINED: %s — draining from routing '
            'and replacing', rid, url, reason)
        if self.quarantine_hook is not None:
            self.quarantine_hook(url, rid, reason)

    # -- SLO evaluation (docs/observability.md "SLOs and alerting") --------
    # Sync ticks between objective-config re-reads: `serve update`
    # adding/changing the `slo:` section must arm the RUNNING LB, so
    # the spec is re-read on this cadence (30 ticks = ~30s at the 1s
    # production sync) and the evaluator rebuilds only on a real
    # config change. One narrow read per cadence, not per tick.
    _SLO_RELOAD_TICKS = 30

    def _emit_slo_transitions(self,  # holds: event-loop
                              transitions: List[dict]) -> None:
        for tr in transitions:
            log = (logger.warning if tr['tier'] == 'page'
                   else logger.info)
            log('SLO %s alert %s: %s (burn %s/%s)', tr['tier'],
                tr['state'], tr['objective'], tr['burn_short'],
                tr['burn_long'])
            if self.slo_transition_hook is not None:
                self.slo_transition_hook(tr)
            self._fleet_event('slo_alert', objective=tr['objective'],
                              tier=tr['tier'], state=tr['state'])
            if tr['tier'] == 'page' and tr['state'] == 'firing':
                self._slo_pending.add(tr['objective'])

    async def _load_slo(self, now: float) -> None:
        """(Re)load objectives: the SKY_TPU_LB_SLO env JSON wins (a
        stand-alone LB without a service row, process-static), else
        the service spec's `slo:` section. A malformed config logs
        and leaves the layer as-is — alerting must never keep the LB
        from serving. The reload clock is only advanced AFTER the
        spec read succeeds: a transient DB hiccup (swallowed by
        _sync_once's fail-open except, like every other sync read)
        retries next tick instead of waiting out a reload period."""
        cfg = None
        raw = os.environ.get(slo_lib.SLO_ENV)
        if raw:
            try:
                cfg = json.loads(raw)
            except ValueError:
                logger.warning('malformed %s JSON; ignoring',
                               slo_lib.SLO_ENV)
        if cfg is None:
            record = await self._offload(
                serve_state.get_service, self.service_name)
            if record is not None:
                cfg = record['spec'].get('slo')
        self._slo_reload_tick = (self._sync_tick
                                 + self._SLO_RELOAD_TICKS)
        try:
            objectives = slo_lib.objectives_from_spec(cfg)
        except exceptions.InvalidTaskError as e:
            # Config error, fail as-is: `serve up`/`update` validate
            # the spec path; this catches the env override and
            # version skew.
            logger.warning('invalid SLO config ignored: %s', e)
            return
        norm = [o.to_config() for o in objectives]
        if norm == (self._slo_cfg or []):
            return   # unchanged: keep the evaluator's burn history
        self._slo_cfg = norm
        if self.slo is not None:
            # A replaced evaluator must not leave dangling 'firing'
            # edges: resolve them (logged + hooked like any
            # transition) so firing/resolved stay paired in the log;
            # a still-ongoing burn re-fires on the successor.
            self._emit_slo_transitions(self.slo.disarm(now))
        if objectives:
            self.slo = slo_lib.SloEvaluator(objectives)
            logger.info('SLO evaluator armed: %s',
                        [o.key for o in objectives])
        else:
            self.slo = None
            logger.info('SLO objectives removed; alerting disarmed')

    async def _slo_tick(self, now: float) -> None:
        """One burn-rate evaluation pass, riding the sync tick (so
        the twin drives it at virtual cadence): ingest outcome-counter
        deltas + replica freshness, evaluate every (objective, tier)
        pair, and turn page-tier firing edges into flight-recorder
        fleet dumps."""
        if self._sync_tick >= self._slo_reload_tick:
            await self._load_slo(now)
        if self.slo is not None:
            self.slo.ingest_counters({
                'total': self._requests_total,
                'failed': self._requests_failed,
                'no_replica': self._requests_no_replica,
                'shed': self._requests_shed,
                'tenants': {t: (rec['total'], rec['shed'],
                                rec['failed'], rec['no_replica'])
                            for t, rec in self._tenants.items()},
            }, now)
            stale = self._stale_rings()
            with_ring = [u for u, r in self._replica_history.items()
                         if len(r) >= 2]
            self.slo.note_replica_freshness(
                len(with_ring) - len(stale), len(stale), now)
            self._emit_slo_transitions(self.slo.evaluate(now))
        # OUTSIDE the armed-guard on purpose: a rate-limit-deferred
        # page dump stays owed even if a `serve update` disarmed the
        # objectives meanwhile — the edge is the incident (the
        # breaker-edge rule), and the evidence must still land.
        await self._dump_slo_edges(now)

    async def _dump_slo_edges(self, now: float) -> None:
        """Every page-tier firing comes with evidence: snapshot the
        fleet metrics history into the span store (the same black box
        a breaker edge writes), rate-limited per the dump interval
        with the breaker rule — a deferred edge stays owed, so a
        second objective paging inside the interval dumps on a later
        tick instead of losing the incident."""
        if not self._slo_pending:
            return
        min_s = stepline_lib.dump_interval_s()
        if min_s > 0 and now - self._slo_dump_at < min_s:
            return
        firing, self._slo_pending = sorted(self._slo_pending), set()
        self._slo_dump_at = now
        spans = self._fleet_dump_spans(
            'slo_page', {'objectives': firing})
        await self._offload(stepline_lib.write_dump_sync, spans)

    async def _dump_quarantine_edges(self, now: float) -> None:
        """Quarantine evidence dump (docs/robustness.md "Data
        integrity"): same owed-edge rate-limit rule as breaker/SLO
        dumps — a deferred quarantine dump lands on a later tick, the
        replica names ride in the pending set."""
        if not self._quarantine_pending:
            return
        min_s = stepline_lib.dump_interval_s()
        if min_s > 0 and now - self._quarantine_dump_at < min_s:
            return
        urls, self._quarantine_pending = (
            sorted(self._quarantine_pending), set())
        self._quarantine_dump_at = now
        spans = self._fleet_dump_spans(
            'quarantine', {'replicas_quarantined': urls})
        await self._offload(stepline_lib.write_dump_sync, spans)

    # -- scale-to-zero parking (docs/cost.md "Scale to zero") --------------
    def _new_waiter(self):  # holds: event-loop
        """One parked request's wake handle. Seam: the digital twin
        overrides this to hand out its kernel's SimFuture — the
        trampoline rejects foreign awaitables, and parked requests
        must suspend in virtual time."""
        return asyncio.get_running_loop().create_future()

    @staticmethod
    def _resolve_waiter(waiter, value: bool) -> None:
        if not waiter.done():
            waiter.set_result(value)

    async def _wake_tick(self) -> None:
        """Riding the sync tick: reload the wake policy from the
        service spec (same cadence as the SLO reload) and settle
        parked requests — ALL of them wake the moment the ready set
        is non-empty; expired ones shed. No per-request timers: the
        tick is the timeout wheel, which is also what lets the twin
        replay parking deterministically."""
        if self._sync_tick >= self._wake_reload_tick:
            record = await self._offload(
                serve_state.get_service, self.service_name)
            # Clock advances only after a successful read (the
            # _load_slo rule): a DB hiccup retries next tick.
            self._wake_reload_tick = (self._sync_tick
                                      + self._SLO_RELOAD_TICKS)
            # Controller crash-recoveries (PR 14 journal) surface as
            # `recoveries_total` deltas on the service row we just
            # read anyway — a free flight-recorder signal, so an
            # exported incident's timeline shows the control-plane
            # crash between the reclaim and the page.
            rec_total = int((record or {}).get('recoveries_total')
                            or 0)
            if (self._recoveries_seen is not None
                    and rec_total > self._recoveries_seen):
                self._fleet_event(
                    'controller_recovered',
                    recoveries=rec_total - self._recoveries_seen)
            self._recoveries_seen = rec_total
            pol = (((record or {}).get('spec') or {})
                   .get('replica_policy') or {})
            if (pol.get('min_replicas') == 0
                    and pol.get('wake_on_request')):
                self._wake_cfg = {
                    'max_parked': max(1, int(
                        pol.get('max_parked_requests') or 32))}
            else:
                self._wake_cfg = None
        if not self._parked:
            return
        now = self._clock.monotonic()
        if self.policy.ready_urls:
            # Capacity is back: one cold-start sample per wake EVENT
            # (not per parked request) — the stopwatch started when
            # the first request parked against the empty fleet.
            if self._wake_started_t is not None:
                self._cold_starts.append(now - self._wake_started_t)
                self._cold_starts_total += 1
                self._wake_started_t = None
            woke, self._parked = self._parked, []
            for entry in woke:
                self._resolve_waiter(entry['waiter'], True)
            return
        still: List[dict] = []
        for entry in self._parked:
            if now >= entry['deadline']:
                self._resolve_waiter(entry['waiter'], False)
            else:
                still.append(entry)
        self._parked = still

    async def _park_for_wake(self, counted: bool = False) -> bool:
        """Park the current request until the fleet wakes. True =
        capacity arrived (re-select and serve); False = parking is
        off, the queue is full, or the wake timed out (fall through
        to the 503 branch). While parked the request counts as
        in-flight — that gauge is exactly the queue signal
        QueueLengthAutoscaler wakes a zero-replica fleet on.
        ``counted``: the caller already holds an inflight increment
        (the mid-retry path), so don't double-count the gauge."""
        cfg = self._wake_cfg
        if cfg is None or len(self._parked) >= cfg['max_parked']:
            return False
        now = self._clock.monotonic()
        if self._wake_started_t is None and not self.policy.ready_urls:
            self._wake_started_t = now
        waiter = self._new_waiter()
        self._parked.append({'waiter': waiter,
                             'deadline': now + WAKE_TIMEOUT_S})
        self._parked_total += 1
        if not counted:
            self._inflight += 1
        try:
            return bool(await waiter)
        finally:
            # The normal request path re-increments after selection.
            if not counted:
                self._inflight -= 1

    async def _stats_loop(self) -> None:
        while self._running:
            await asyncio.sleep(self.stats_flush_s)
            await self._flush_stats_once()

    async def _flush_stats_once(self) -> None:
        """One stats flush (factored out of the loop for the twin)."""
        n, self._pending_requests = self._pending_requests, 0
        try:
            if n:
                await self._offload(
                    serve_state.record_requests, self.service_name, n,
                    self._clock.time())
            # In-flight gauge: the queue-depth signal for
            # QueueLengthAutoscaler (requests accepted but not yet
            # finished across all replicas).
            await self._offload(
                serve_state.set_inflight, self.service_name,
                self._inflight)
            # Scheduler backlog inside the engines (summed
            # num_waiting): lets QueueLengthAutoscaler scale on
            # real queued work, not LB in-flight counts alone.
            await self._offload(
                serve_state.set_queue_depth, self.service_name,
                sum(self._replica_queue_depth.values()))
            if self.slo is not None:
                # SLO-class scaling input: the max page-tier burn
                # rate, read by the autoscaler as a scale-up signal
                # (docs/observability.md "SLOs and alerting"). The
                # flush cadence rides along so the reader's staleness
                # window scales with it.
                await self._offload(
                    serve_state.set_slo_burn, self.service_name,
                    self.slo.page_burn(self._clock.time()),
                    self.stats_flush_s)
        except Exception:  # noqa: BLE001
            logger.warning('stats flush failed', exc_info=True)

    # -- request path ------------------------------------------------------
    # NOTE: JSON (not the API server's Prometheus registry) stays the
    # default — the LB runs as its own process on the serve controller
    # and this shape feeds `serve status` directly;
    # `?format=prometheus` wraps lb_metrics() in text exposition
    # (observability/prometheus.py) for scrape-based stacks.
    # Tenant ids are client-controlled: bound the per-tenant map so an
    # id-minting client cannot grow LB memory (or /-/metrics payloads)
    # without limit — oldest-created entries are evicted at the cap.
    _MAX_TENANTS = 1024

    def _tenant(self, tenant: str) -> dict:  # holds: event-loop
        rec = self._tenants.get(tenant)
        if rec is None:
            while len(self._tenants) >= self._MAX_TENANTS:
                self._tenants.pop(next(iter(self._tenants)))
            rec = self._tenants[tenant] = {
                'total': 0, 'shed': 0, 'failed': 0, 'no_replica': 0,
                'ttfts': collections.deque(maxlen=1024)}
        return rec

    def _note_ttft(self, value: float,  # holds: event-loop
                   tenant: Optional[str]) -> None:
        self._ttfts.append(value)
        if tenant:
            self._tenant(tenant)['ttfts'].append(value)
        if self.slo is not None:
            self.slo.note_latency('ttft', value, tenant,
                                  self._clock.time())

    def _note_itl(self, gap: float,  # holds: event-loop
                  tenant: Optional[str]) -> None:
        self._itls.append(gap)
        if self.slo is not None:
            self.slo.note_latency('itl', gap, tenant,
                                  self._clock.time())

    def _note_failed(self,  # holds: event-loop
                     tenant: Optional[str]) -> None:
        """One replica-side failure the client could see — the edge
        counter plus the per-tenant ledger the availability SLO
        ingests by delta."""
        self._requests_failed += 1
        if tenant:
            self._tenant(tenant)['failed'] += 1

    def _stale_rings(self) -> Set[str]:  # holds: event-loop
        """The PR 12 freshest-ring staleness rule, as a set: rings
        (len >= 2) whose replica has stopped reporting. A
        ready-but-unresponsive replica's ring stops appending
        (fetches fail) but survives pruning — its frozen window must
        not contribute a constant phantom rate to the fleet gauges
        (or silently mask a fleet-wide SLO burn: the evaluator counts
        these BAD). Two complementary signals: a ring whose newest
        sample lags the freshest ring's by a few sync ticks
        (relative, not wall-clock, so replayed/synthetic histories
        still aggregate), and a ring whose last successful fetch lags
        the sync-tick COUNTER — the counter advances even when every
        fetch fails, which catches the all-frozen fleet the relative
        check cannot (a lone hung replica's ring is its own
        freshest)."""
        newest = max((ring[-1]['t']
                      for ring in self._replica_history.values()
                      if ring), default=0.0)
        stale_s = 3 * self.sync_interval_s
        stale_ticks = 3
        stale: Set[str] = set()
        for url, ring in self._replica_history.items():
            if len(ring) < 2:
                continue
            if newest - ring[-1]['t'] > stale_s:
                stale.add(url)   # frozen ring: stopped reporting
            elif (self._sync_tick - self._history_tick.get(
                    url, self._sync_tick)) > stale_ticks:
                stale.add(url)   # fetches failing: fleet may be dark
        return stale

    def _history_gauges(self) -> Dict[str, object]:  # holds: event-loop
        """Windowed-rate gauges derived from the per-replica history
        rings (counter DELTAS over each ring's span — the flight
        recorder's fleet tier): the shape the catalog autoscaler and
        the digital twin consume. Internal names; the emitted keys
        live in ``lb_metrics`` (SKY-REGISTRY)."""
        window = 0.0
        tps = 0.0
        any_tps = False
        d_hits = 0
        d_lookups = 0
        stale = self._stale_rings()
        for url, ring in self._replica_history.items():
            if len(ring) < 2 or url in stale:
                continue
            a, b = ring[0], ring[-1]
            span = b['t'] - a['t']
            if span <= 0:
                continue
            window = max(window, span)
            if (a.get('decode_tokens') is not None
                    and b.get('decode_tokens') is not None):
                tps += max(0, b['decode_tokens']
                           - a['decode_tokens']) / span
                any_tps = True
            if (a.get('prefix_hits') is not None
                    and b.get('prefix_hits') is not None):
                dh = max(0, b['prefix_hits'] - a['prefix_hits'])
                dm = max(0, (b.get('prefix_misses') or 0)
                         - (a.get('prefix_misses') or 0))
                d_hits += dh
                d_lookups += dh + dm
        return {
            'window_s': round(window, 3) if window else None,
            'tokens_per_sec': round(tps, 4) if any_tps else None,
            'hit_rate': (round(d_hits / d_lookups, 4)
                         if d_lookups else None),
        }

    def lb_history(self) -> Dict[str, object]:  # holds: event-loop
        """The raw per-replica history rings (``/-/metrics/history``):
        one row per sync tick per replica, oldest first."""
        return {
            'history_len': HISTORY_LEN,
            'sync_interval_s': self.sync_interval_s,
            'replicas': {u: list(ring) for u, ring in
                         sorted(self._replica_history.items())},
        }

    def lb_metrics(self) -> Dict[str, object]:  # holds: event-loop
        ttfts = sorted(self._ttfts)
        itls = sorted(self._itls)
        hist = self._history_gauges()
        cold = sorted(self._cold_starts)
        cost = self._cost_gauges or {}
        cost_rate = float(cost.get('cost_per_hour') or 0.0)
        tps_w = hist['tokens_per_sec']
        # $/h over (tokens/s * 3600 s/h / 1000) = $ per 1k tokens;
        # null until both a billed rate and a windowed token rate
        # exist (an idle or unpriced fleet has no unit cost).
        cost_per_1k = (round(cost_rate / (tps_w * 3.6), 6)
                       if cost_rate > 0 and tps_w else None)

        def pct(vals, p: float):
            if not vals:
                return None
            return vals[min(len(vals) - 1, int(len(vals) * p))]

        def tenant_row(rec: dict) -> dict:
            tt = sorted(rec['ttfts'])
            return {'requests_total': rec['total'],
                    'requests_shed': rec['shed'],
                    'requests_failed': rec.get('failed', 0),
                    'requests_no_replica': rec.get('no_replica', 0),
                    'ttft_p50_s': pct(tt, 0.50),
                    'ttft_p99_s': pct(tt, 0.99),
                    'ttft_samples': len(tt)}
        now = self._clock.time()
        return {
            'tenants': {t: tenant_row(rec)
                        for t, rec in sorted(self._tenants.items())},
            'engine_queue_depth': sum(
                self._replica_queue_depth.values()),
            'replica_queue_depth': dict(self._replica_queue_depth),
            # Fleet decode efficiency (speculative decoding): mean of
            # each reporting replica's gauge — null until a ready
            # replica reports one.
            'engine_tokens_per_step': _mean_gauge(
                self._replica_decode_stats, 'tokens_per_step'),
            'engine_accepted_len_mean': _mean_gauge(
                self._replica_decode_stats, 'accepted_len_mean'),
            'engine_spec_accept_rate': _mean_gauge(
                self._replica_decode_stats, 'spec_accept_rate'),
            # Windowed-rate gauges from the fleet history rings
            # (counter deltas over the retained window; the raw rings
            # are at /-/metrics/history): null until two sync ticks
            # of history exist.
            'history_window_s': hist['window_s'],
            'engine_tokens_per_sec_w': hist['tokens_per_sec'],
            'prefix_hit_rate_w': hist['hit_rate'],
            'requests_total': self._requests_total,
            'requests_failed': self._requests_failed,
            'requests_no_replica': self._requests_no_replica,
            'requests_retried': self._requests_retried,
            'requests_resumed': self._requests_resumed,
            'requests_shed': self._requests_shed,
            'draining': list(self._draining_urls),
            'ttft_p50_s': pct(ttfts, 0.50),
            'ttft_p90_s': pct(ttfts, 0.90),
            'ttft_p99_s': pct(ttfts, 0.99),
            'ttft_samples': len(ttfts),
            'itl_p50_s': pct(itls, 0.50),
            'itl_p99_s': pct(itls, 0.99),
            'itl_samples': len(itls),
            'ready_replicas': len(self.policy.ready_urls),
            'breaker': self.breaker.snapshot(),
            # SLO layer (docs/observability.md "SLOs and alerting"):
            # null/zero until the service declares objectives.
            'slo': (self.slo.gauges(now)
                    if self.slo is not None else None),
            'slo_alerts_firing': (len(self.slo.firing())
                                  if self.slo is not None else 0),
            'slo_page_alerts_firing': (
                len(self.slo.firing('page'))
                if self.slo is not None else 0),
            'slo_burn': (self.slo.page_burn(now)
                         if self.slo is not None else 0.0),
            # Fleet cost plane (docs/cost.md): controller-flushed
            # economics gauges + the LB-side unit cost and the
            # scale-to-zero wake ledger. Zero/null until the cost
            # plane prices the fleet.
            'fleet_cost_per_hour': cost_rate,
            'cost_per_1k_good_tokens': cost_per_1k,
            'spot_fraction': float(cost.get('spot_fraction') or 0.0),
            'cost_catalog_stale': int(cost.get('catalog_stale') or 0),
            'parked_requests': len(self._parked),
            'cold_starts_total': self._cold_starts_total,
            'cold_start_p50_s': (round(pct(cold, 0.50), 3)
                                 if cold else None),
            # Data-integrity plane (docs/robustness.md "Data
            # integrity"): golden-probe canaries + quarantine ledger.
            # probe_interval_s is null when probes are unarmed (no
            # golden fixture for the served model).
            'replicas_quarantined': self._replicas_quarantined,
            'probe_failures_total': self._probe_failures,
            'probe_interval_s': self.probe_interval_s,
            'quarantined': sorted(self._quarantined_urls),
            # Incident replay plane (docs/simulation.md): evidence-
            # ring write cursors. `.total` is monotonic, so export
            # tooling (and the no-silent-caps truncation warning)
            # can tell how much history fell off each ring.
            'incident_request_events_total': (
                self._request_events.total),
            'incident_fleet_events_total': self._fleet_events.total,
            # Fleet prefix tier (docs/serving.md "Disaggregated
            # prefill/decode"): LB routing hit rate + the replica KV
            # streaming counters rolled up from the same sync-tick
            # fetch that feeds the index.
            'fleet_prefix_hit_rate': (
                round(self._fleet_hits / self._fleet_lookups, 4)
                if self._fleet_lookups else None),
            'fleet_prefix_pages': self.fleet_index.total_pages(),
            'kv_transfers_total': sum(
                int(r.get('kv_transfers_total') or 0)
                for r in self._replica_decode_stats.values()),
            'kv_transfer_bytes': sum(
                int(r.get('kv_transfer_bytes') or 0)
                for r in self._replica_decode_stats.values()),
            'kv_transfer_failures': sum(
                int(r.get('kv_transfer_failures') or 0)
                for r in self._replica_decode_stats.values()),
            # Worst replica tail, not a mean of p99s — the fleet's
            # transfer SLI is its slowest link.
            'kv_transfer_p99_s': max(
                (r['kv_transfer_p99_s']
                 for r in self._replica_decode_stats.values()
                 if r.get('kv_transfer_p99_s') is not None),
                default=None),
        }

    def _select_fleet(self, chain: List[int], candidates: List[str]
                      ) -> Optional[str]:  # holds: event-loop
        """Fleet-index tier of _select (docs/serving.md "Disaggregated
        prefill/decode"). Three outcomes: a replica already HOLDING the
        longest indexed prefix (least-load tiebreak among equal-depth
        holders, prefill replicas excluded — they donate, not decode);
        a decode/mixed replica with ``_pending_donor`` armed so it
        PULLS the prefix from the best holder; or None — no fleet
        opinion, the caller falls through to the consistent-hash ring
        and the base policy. Deterministic given equal state: every
        tiebreak is (load, url)-ordered."""
        roles = self.fleet_index.role
        depth, holders = self.fleet_index.lookup(chain)
        if depth > 0:
            live = [u for u in holders if u in candidates
                    and self.breaker.allows(u)]
            serving = [u for u in live if roles(u) != 'prefill']
            if serving:
                best = min(serving, key=lambda u:
                           (self.policy.load(u), u))
                # Warm-set expansion: when even the least-loaded
                # holder is busier than a cold replica, a transfer is
                # cheaper than queuing behind it — replicate the
                # prefix onto the least-loaded decode replica (it
                # pulls from the holder). Under steady load the warm
                # set grows to the offered concurrency; the replicas'
                # idle TTL trims it back when load recedes.
                if self.policy.load(best) > 0:
                    rest = [u for u in candidates
                            if u not in serving
                            and roles(u) != 'prefill'
                            and self.breaker.allows(u)]
                    if rest:
                        grow = min(rest, key=lambda u:
                                   (self.policy.load(u), u))
                        if self.policy.load(grow) \
                                < self.policy.load(best):
                            # Donor preference: a prefill-pool holder
                            # (prefill-and-donate is its job — keeps
                            # export load off busy decode replicas),
                            # else the holder we would have queued on.
                            pre = [u for u in holders
                                   if roles(u) == 'prefill']
                            self._pending_donor = (
                                min(pre) if pre else best)
                            return grow
                return best
            # A holder exists but cannot serve (prefill role, tried,
            # breaker-open): route a decode/mixed replica and have it
            # pull the prefix from the holder. The donor only answers
            # /kv/export — it need not be admissible for serving.
            pool = ([u for u in candidates if roles(u) != 'prefill']
                    or candidates)
            admissible = ([u for u in pool if self.breaker.allows(u)]
                          or pool)
            self._pending_donor = holders[0]
            return min(admissible, key=lambda u:
                       (self.policy.load(u), u))
        # Cold prefix = first-chunk work: steer it to the prefill pool
        # (it prefills, caches, and donates from then on). All-mixed
        # fleets have no pool, so this is a no-op by default.
        pre = [u for u in candidates
               if roles(u) == 'prefill' and self.breaker.allows(u)]
        if pre:
            return min(pre, key=lambda u: (self.policy.load(u), u))
        return None

    def _select(self, tried: Set[str],
                affinity: Optional[str] = None,
                chain: Optional[List[int]] = None) -> Optional[str]:
        """Pick the next replica: any replica the fleet prefix index
        says holds the longest cached prefix of this prompt (see
        _select_fleet), else the affinity-preferred replica (the
        cache-aware policy's consistent-hash home for this prompt
        prefix) when it is admissible, else the policy's choice if its
        breaker admits it, else the first admissible candidate. If
        EVERY breaker is open, fail open with any untried replica —
        turning a possibly-wrong breaker into a total blackout is worse
        than one wasted probe."""
        self._pending_donor = None
        candidates = [u for u in self.policy.ready_urls
                      if u not in tried
                      and u not in self._quarantined_urls]
        if not candidates:
            return None
        if chain and self.fleet_routing and self.fleet_index.armed:
            pick = self._select_fleet(chain, candidates)
            if pick is not None:
                return pick
        if affinity is not None:
            preferred = self.policy.preferred_replica(affinity)
            # Breaker-open (or already-tried) preferred replica: fall
            # through to the base policy below instead of routing into
            # a corpse just to keep the cache warm.
            if (preferred in candidates
                    and self.breaker.allows(preferred)):
                return preferred
        blocked: Set[str] = set()
        # Bounded walk of policy picks (least-load may repeat itself).
        for _ in range(len(self.policy.ready_urls) + 1):
            url = self.policy.select_replica()
            if url is None:
                break
            # The policy walks its own ready list, which still holds a
            # just-quarantined url until the sync tick prunes it — the
            # candidates filter must bind this path too.
            if url in tried or url in blocked or url not in candidates:
                continue
            if self.breaker.allows(url):
                return url
            blocked.add(url)
            if len(blocked) == len(candidates):
                break
        for url in candidates:
            if url not in blocked and self.breaker.allows(url):
                return url
        # Every untried candidate's breaker is open: fail open with one
        # anyway (a possibly-wrong breaker must not become a blackout).
        return candidates[0]

    async def _proxy_attempt(self, request: web.Request, url: str,
                             body: bytes, headers: Dict[str, str],
                             t_arrival: float, gen: bool = False,
                             tenant: Optional[str] = None):
        """One proxy attempt to ``url``. Raises _PreStreamFailure when
        nothing has been sent to the client yet (retryable); any
        response it returns has been (at least partially) delivered.
        Returns ``(resp, replica_ok)`` — ``replica_ok`` False means the
        replica misbehaved even though bytes were delivered (died
        mid-stream, or answered 5xx): not retryable, but a breaker
        failure all the same, so a listening-but-wedged replica that
        500s every request still trips out of the rotation."""
        resp: Optional[web.StreamResponse] = None
        # LB → replica is a traced hop: adopt the caller's context (if
        # any) and pass ours downstream, so serve-path TTFT decomposes
        # into LB time vs replica time. Span recording closes with the
        # proxied response (stack.aclose() in the finally); the proxy
        # loop stays allocation-free when tracing is off.
        stack = contextlib.AsyncExitStack()
        try:
            target = url.rstrip('/') + request.path_qs
            if trace_lib.enabled():
                with contextlib.suppress(Exception):
                    stack.enter_context(trace_lib.context_from(
                        request.headers.get(trace_lib.HEADER)))
                    stack.enter_context(trace_lib.span(
                        'lb.proxy', hop='serve-lb', replica=url,
                        path=request.path))
                    trace_lib.inject_headers(headers)
            try:
                # Chaos seam: an injected error here behaves exactly
                # like a replica that died pre-stream (failover +
                # breaker bookkeeping), no real replica kill needed.
                await failpoints.hit_async('lb.proxy')
            except failpoints.FailpointError as e:
                raise _PreStreamFailure(e) from e
            assert self._session is not None
            try:
                upstream_cm = self._session.request(
                    request.method, target, headers=headers,
                    data=body or None, allow_redirects=False)
                upstream = await stack.enter_async_context(upstream_cm)
            except (aiohttp.ClientError, asyncio.TimeoutError) as e:
                raise _PreStreamFailure(e) from e
            if gen and upstream.status in (429, 503):
                # Shed, not dead: admission-full or draining. Nothing
                # reached the client yet, so route around it. /generate
                # only — for arbitrary proxied endpoints a 5xx keeps
                # feeding the breaker below.
                raise _ReplicaSaturated(
                    upstream.status, await upstream.read(),
                    dict(upstream.headers))
            # Replica-level errors are failures for the metrics even
            # though we faithfully proxy them — and their (instant)
            # latency must not pollute the TTFT distribution.
            upstream_ok = upstream.status < 500
            if not upstream_ok:
                self._note_failed(tenant)
            try:
                resp = web.StreamResponse(
                    status=upstream.status,
                    headers={k: v for k, v in upstream.headers.items()
                             if k.lower() not in _HOP_HEADERS})
                # Client-side write failures must NEVER look like
                # replica failures (aiohttp raises its ClientError-
                # derived ClientConnectionResetError on writes to a
                # gone client, which the upstream-error handler below
                # would otherwise swallow as a mid-stream death and
                # feed the breaker): every write to the client converts
                # to _ClientGone, which releases the breaker instead.
                try:
                    await resp.prepare(request)
                except (ConnectionError, OSError) as e:
                    raise _ClientGone(e) from e
                first = True
                t_prev = None
                # Only token streams feed the ITL metric: a
                # non-streaming body that merely spans several 64KB
                # chunks would contribute microsecond gaps and drag
                # itl_p50 toward zero.
                is_token_stream = 'jsonlines' in (
                    upstream.headers.get('Content-Type') or '')
                # Each gap is recorded one chunk LATE so the stream's
                # final gap — the terminal done/tail-flush line landing
                # microseconds after the last token — is dropped
                # instead of dragging itl_p50 toward zero.
                pending_gap = None
                async for chunk in upstream.content.iter_chunked(
                        64 * 1024):
                    now = self._clock.monotonic()
                    if upstream_ok:
                        if first:
                            self._note_ttft(now - t_arrival, tenant)
                        elif is_token_stream:
                            # Gap between flushed lines = the
                            # client-observed inter-token latency.
                            if pending_gap is not None:
                                self._note_itl(pending_gap, tenant)
                            pending_gap = now - t_prev
                    first = False
                    t_prev = now
                    try:
                        await resp.write(chunk)
                    except (ConnectionError, OSError) as e:
                        raise _ClientGone(e) from e
                if first and upstream_ok:  # empty body: headers counted
                    self._note_ttft(self._clock.monotonic() - t_arrival,
                                    tenant)
                with contextlib.suppress(ConnectionError, OSError):
                    await resp.write_eof()
                return resp, upstream_ok
            except _ClientGone:
                raise
            except (aiohttp.ClientError, asyncio.TimeoutError) as e:
                # Only UPSTREAM trouble reaches here now (client-side
                # writes raise _ClientGone above).
                if resp is None or not resp.prepared:
                    raise _PreStreamFailure(e) from e
                # Headers (and possibly body) already went out and this
                # body is not a resumable token stream: a 502 now would
                # corrupt the stream with a second status line, and a
                # retry would replay delivered bytes. Terminate the
                # response; the truncation IS the client's error
                # signal. (A 5xx upstream was already counted failed
                # above — don't count it twice.)
                if upstream_ok:
                    self._note_failed(tenant)
                logger.warning('replica %s died mid-stream: %s', url, e)
                with contextlib.suppress(Exception):
                    await resp.write_eof()
                return resp, False
        finally:
            with contextlib.suppress(Exception):
                await stack.aclose()

    def _admit_stream_line(self, splice: _StreamSplice, line: bytes,
                           t_arrival: float
                           ) -> Optional[bytes]:  # holds: event-loop
        """Process one COMPLETE upstream jsonlines line: record
        TTFT/ITL, add its token ids to the delivered ledger, and stamp
        the resume count onto the done line. Returns the bytes to
        forward, or None when the line is a server-side error report
        (an in-stream replica failure — resumable, not payload)."""
        try:
            obj = json.loads(line)
        except ValueError:
            obj = None
        if isinstance(obj, dict) and 'error' in obj:
            return None
        now = self._clock.monotonic()
        if splice.first:
            self._note_ttft(now - t_arrival, splice.tenant)
            splice.first = False
        else:
            # One line late, same as the plain proxy: the terminal
            # done-line gap is dropped instead of dragging itl_p50.
            if splice.pending_gap is not None:
                self._note_itl(splice.pending_gap, splice.tenant)
            splice.pending_gap = now - (splice.t_prev or now)
        splice.t_prev = now
        if not isinstance(obj, dict):
            return line + b'\n'     # opaque line: forward verbatim
        if obj.get('done'):
            splice.done = True
            if splice.resumes:
                obj['resumed'] = splice.resumes
                return json.dumps(obj).encode() + b'\n'
            return line + b'\n'
        toks = obj.get('tokens')
        if isinstance(toks, list):
            splice.delivered.extend(int(t) for t in toks)
        return line + b'\n'

    async def _proxy_stream_attempt(
            self, request: web.Request, url: str,
            headers: Dict[str, str], t_arrival: float,
            splice: _StreamSplice):
        """One leg of a resumable /generate token stream against
        ``url``. Forwards complete jsonlines lines into the (single)
        client response; raises _UpstreamDead on ANY replica-side
        failure before the done line (the handler resumes on the next
        replica), _ClientGone on client-side write failures, and
        _ReplicaSaturated on a pre-stream shed."""
        stack = contextlib.AsyncExitStack()
        splice.buf = b''    # a dead leg's partial line is DISCARDED
        try:
            target = url.rstrip('/') + request.path_qs
            if trace_lib.enabled():
                with contextlib.suppress(Exception):
                    stack.enter_context(trace_lib.context_from(
                        request.headers.get(trace_lib.HEADER)))
                    stack.enter_context(trace_lib.span(
                        'lb.proxy', hop='serve-lb', replica=url,
                        path=request.path))
                    trace_lib.inject_headers(headers)
            try:
                await failpoints.hit_async('lb.proxy')
            except failpoints.FailpointError as e:
                raise _UpstreamDead(e) from e
            assert self._session is not None
            try:
                upstream_cm = self._session.request(
                    request.method, target, headers=headers,
                    data=splice.body(), allow_redirects=False)
                upstream = await stack.enter_async_context(upstream_cm)
            except (aiohttp.ClientError, asyncio.TimeoutError) as e:
                raise _UpstreamDead(e) from e
            ctype = upstream.headers.get('Content-Type') or ''
            if upstream.status != 200 or 'jsonlines' not in ctype:
                if upstream.status in (429, 503):
                    raise _ReplicaSaturated(
                        upstream.status, await upstream.read(),
                        dict(upstream.headers))
                if splice.resp is not None:
                    # Mid-splice a non-stream answer cannot be relayed
                    # (headers are gone); treat as a dead upstream.
                    raise _UpstreamDead(RuntimeError(
                        f'replica answered {upstream.status} on a '
                        f'resume leg'))
                # Plain (non-stream) answer — 400s, engine-died 500s:
                # relay it exactly like the non-resumable path.
                if upstream.status >= 500:
                    self._note_failed(splice.tenant)
                data = await upstream.read()
                resp = web.Response(
                    status=upstream.status, body=data,
                    headers={k: v for k, v in upstream.headers.items()
                             if k.lower() not in _HOP_HEADERS})
                return resp, upstream.status < 500
            if splice.resp is None:
                resp = web.StreamResponse(
                    status=200,
                    headers={k: v for k, v in upstream.headers.items()
                             if k.lower() not in _HOP_HEADERS})
                try:
                    await resp.prepare(request)
                except (ConnectionError, OSError) as e:
                    raise _ClientGone(e) from e
                splice.resp = resp
            try:
                async for chunk in upstream.content.iter_chunked(
                        64 * 1024):
                    splice.buf += chunk
                    while True:
                        line, sep, rest = splice.buf.partition(b'\n')
                        if not sep:
                            break
                        splice.buf = rest
                        if not line.strip():
                            continue
                        out = self._admit_stream_line(splice, line,
                                                      t_arrival)
                        if out is None:
                            raise _UpstreamDead(RuntimeError(
                                'replica reported an in-stream error'))
                        try:
                            await splice.resp.write(out)
                        except (ConnectionError, OSError) as e:
                            raise _ClientGone(e) from e
                        if splice.done:
                            break
                        # Chaos seam: sever THIS leg exactly as if the
                        # replica died under the stream (drives the
                        # resume path without killing anything real).
                        try:
                            await failpoints.hit_async(
                                'serve.lb.midstream_kill')
                        except failpoints.FailpointError as e:
                            raise _UpstreamDead(e) from e
                        # A probe quarantined THIS replica under the
                        # stream: cut at the line boundary (every
                        # delivered line predates the verdict and is
                        # ledgered) and resume elsewhere — the splice
                        # keeps the client stream bit-identical.
                        if url in self._quarantined_urls:
                            raise _QuarantineCut()
                    if splice.done:
                        break
            except (_ClientGone, _UpstreamDead, _ReplicaSaturated):
                raise
            except (aiohttp.ClientError, asyncio.TimeoutError,
                    OSError) as e:
                raise _UpstreamDead(e) from e
            if not splice.done:
                # Upstream closed cleanly without a done line: the
                # replica died politely — still a truncation to heal.
                raise _UpstreamDead(ConnectionError(
                    'upstream closed before the done line'))
            try:
                await splice.resp.write_eof()
            except (ConnectionError, OSError) as e:
                raise _ClientGone(e) from e
            return splice.resp, True
        finally:
            with contextlib.suppress(Exception):
                await stack.aclose()

    def _next_url(self, tried: Set[str], affinity: Optional[str],
                  t_deadline: Optional[float],
                  headers: Dict[str, str],
                  chain: Optional[List[int]] = None) -> Optional[str]:
        """Next retry target, deadline-aware: refreshes the forwarded
        deadline header to the REMAINING budget so the next replica's
        engine enforces the same wall-clock cutoff. None when replicas
        or budget ran out."""
        if t_deadline is not None:
            remaining = t_deadline - self._clock.monotonic()
            if remaining <= 0:
                return None
            headers[common.DEADLINE_HEADER] = f'{remaining:.3f}'
        return self._select(tried, affinity, chain)

    async def _next_url_or_wake(self, tried: Set[str],
                                affinity: Optional[str],
                                t_deadline: Optional[float],
                                headers: Dict[str, str],
                                splice,
                                chain: Optional[List[int]] = None
                                ) -> Optional[str]:
        """Pre-stream retry target with the scale-to-zero fallback: a
        request caught mid-retry while the fleet drains to zero (every
        ready replica failed, NO tokens delivered) parks for the wake
        instead of 502ing. Bounded: a stale ready set resolves parks
        immediately, so a few park->reselect cycles may pass before
        the sync loop catches up with reality — cap them so the
        request can't orbit forever."""
        url = self._next_url(tried, affinity, t_deadline, headers,
                             chain)
        if url is not None or self._wake_cfg is None:
            return url
        if splice is not None and (splice.resp is not None
                                   or splice.delivered
                                   or splice.resumes):
            return None   # mid-stream: resume needs a live leg NOW
        for _ in range(4):
            if (t_deadline is not None
                    and self._clock.monotonic() >= t_deadline):
                return None
            if not await self._park_for_wake(counted=True):
                return None
            tried.clear()   # a woken fleet is a NEW fleet
            url = self._next_url(tried, affinity, t_deadline, headers,
                                 chain)
            if url is not None:
                return url
        return None

    async def handle(self, request: web.Request) -> web.StreamResponse:
        if request.path == '/-/urls':   # introspection endpoint
            return web.json_response(
                {'ready_replica_urls': list(self.policy.ready_urls)})
        if request.path == '/-/metrics':
            # JSON by default (feeds `serve status`);
            # `?format=prometheus` wraps the same gauges in text
            # exposition for a scrape-based stack.
            if request.query.get('format') == 'prometheus':
                return web.Response(
                    text=prom_lib.render_lb(self.lb_metrics()),
                    content_type='text/plain', charset='utf-8')
            return web.json_response(self.lb_metrics())
        if request.path == '/-/metrics/history':
            return web.json_response(self.lb_history())
        if request.path == '/-/alerts':
            # Alert state + error-budget view (docs/observability.md
            # "SLOs and alerting"); `sky-tpu slo <lb-url>` reads this.
            if self.slo is None:
                return web.json_response(
                    {'enabled': False, 'objectives': {},
                     'firing': [], 'transitions': []})
            return web.json_response(
                self.slo.snapshot(self._clock.time()))
        self._requests_total += 1
        t_arrival = self._clock.monotonic()
        lb_recv_t = self._clock.time()
        # Body read comes FIRST: nothing is selected or counted yet, so
        # a client disconnecting mid-upload cannot leak the inflight
        # gauge or burn a half-open breaker probe slot.
        body = await request.read()
        headers = {k: v for k, v in request.headers.items()
                   if k.lower() not in _HOP_HEADERS}
        # /generate bodies are parsed once, up front: the resumable-
        # stream splice needs the payload (to re-issue with
        # resume_from) and the cache-aware policy needs the affinity
        # key. Non-generate traffic skips the parse entirely.
        payload: Optional[Dict[str, object]] = None
        if (request.method == 'POST'
                and request.path.endswith('/generate') and body):
            try:
                parsed = json.loads(body)
                payload = parsed if isinstance(parsed, dict) else None
            except ValueError:
                payload = None   # the replica will 400 it
        # The donor header is LB-internal routing state: never honor a
        # client-supplied value (a hostile client could point replicas
        # at arbitrary pull targets).
        headers.pop(common.KV_DONOR_HEADER, None)
        # The request's timeline starts here: every leg (retry and
        # resume legs too) carries the moment this handler was
        # entered, and the replica's flight recorder keeps it.
        headers[common.LB_RECV_HEADER] = f'{lb_recv_t:.6f}'
        # Fleet prefix chain (docs/serving.md "Disaggregated prefill/
        # decode"): token prompts chain into page-block hashes — the
        # key space shared with every replica's radix index. Text
        # prompts (no tokenizer here) stay on the legacy char key.
        chain: Optional[List[int]] = None
        if (payload is not None and self.fleet_routing
                and isinstance(self.policy, lbp.CacheAwarePolicy)):
            page = self.fleet_index.page
            toks = payload.get('tokens')
            if page and isinstance(toks, list) and toks:
                try:
                    chain = prefix_hash.chain_hashes(
                        [int(t) for t in toks], page,
                        limit=self._CHAIN_LIMIT) or None
                except (TypeError, ValueError):
                    chain = None
        # Prefix affinity (cache-aware policy only): same-prefix
        # /generate traffic keeps landing on the same replica so its
        # radix tree actually accumulates hits — keyed from the
        # already-parsed payload, never a second body parse. With the
        # fleet index armed, the key is the chain hash at the longest
        # INDEXED match instead of a fixed-length lead block, so
        # prompts sharing a cached prefix key identically however they
        # diverge afterwards.
        affinity: Optional[str] = None
        if (payload is not None
                and isinstance(self.policy, lbp.CacheAwarePolicy)):
            if chain:
                self._fleet_lookups += 1
                depth, _ = self.fleet_index.lookup(chain)
                if depth > 0:
                    self._fleet_hits += 1
                affinity = lbp.indexed_affinity_key(chain, depth)
            else:
                affinity = lbp.affinity_key_from_payload(payload)
        # Multi-tenant identity (/generate only): the header wins, a
        # 'tenant' body field is the fallback — and is PROMOTED to the
        # header on the forwarded legs so the replica's scheduler sees
        # it without re-parsing the body.
        tenant: Optional[str] = None     # recording label (/generate)
        if payload is not None:
            explicit = (request.headers.get(common.TENANT_HEADER)
                        or str(payload.get('tenant') or '') or None)
            if explicit:
                # Promote a body-only tenant to the header so the
                # replica's scheduler sees it without re-parsing.
                headers[common.TENANT_HEADER] = explicit
            tenant = explicit or 'default'
            self._tenant(tenant)['total'] += 1
        # Token streams are RESUMABLE: mid-stream upstream death is
        # healed by re-issuing to the next replica with the delivered
        # tokens, splicing into the same client response.
        splice = (_StreamSplice(payload, body, tenant=tenant)
                  if payload is not None and payload.get('stream')
                  else None)
        # Per-request wall-clock budget: bounded end to end, forwarded
        # (remaining) on every retry leg, enforced in the engine.
        t_deadline: Optional[float] = None
        hdr = request.headers.get(common.DEADLINE_HEADER)
        if hdr:
            try:
                t_deadline = t_arrival + float(hdr)
            except ValueError:
                t_deadline = None   # the replica will 400 it
        # Flight-recorder arrival record (/generate only): scrubbed
        # at capture, outcome stamped by whichever terminal path this
        # request takes below.
        req_rec = (self._note_request_event(payload, tenant,
                                            t_deadline, t_arrival)
                   if payload is not None else None)
        tried: Set[str] = set()
        url = self._select(tried, affinity, chain)
        if url is None and self._wake_cfg is not None:
            # Scale-to-zero wake (docs/cost.md): park instead of 503.
            # A True wake means the ready set refilled — re-select;
            # False (overflow/timeout) falls through to the shed path.
            if await self._park_for_wake():
                url = self._select(tried, affinity, chain)
        if url is None:
            self._requests_no_replica += 1
            if tenant is not None:
                # The per-tenant availability SLI counts an empty
                # ready set as BAD (the fleet-wide branch already
                # does) — an all-replicas-lost outage must burn the
                # tenant objective too, not read as 100% good.
                self._tenant(tenant)['no_replica'] += 1
            self._finish_event(req_rec, 'no_replica')
            return web.Response(
                status=503,
                # Capacity usually returns within a sync interval or
                # two once a replica recovers; tell clients when to
                # come back instead of letting them hammer.
                headers={'Retry-After': str(max(
                    1, int(self.sync_interval_s * 2)))},
                text=f'No ready replicas for service '
                     f'{self.service_name!r}. Use `sky-tpu serve status` '
                     f'to check replica health.\n')
        self._pending_requests += 1
        self._inflight += 1
        last_cause: Optional[BaseException] = None
        saturated: Optional[_ReplicaSaturated] = None
        try:
            while url is not None:
                current = url
                donor, self._pending_donor = self._pending_donor, None
                if donor and donor != current:
                    try:
                        # Chaos seam (docs/robustness.md "Site
                        # catalog"): a stalled/severed transfer link —
                        # `delay` stalls this leg's dispatch, `error`
                        # drops the donor so the replica recomputes
                        # plain (the fallback the twin's reclaim storm
                        # gates on).
                        await failpoints.hit_async(
                            'serve.lb.kv_transfer_stall')
                        headers[common.KV_DONOR_HEADER] = donor
                    except failpoints.FailpointError:
                        headers.pop(common.KV_DONOR_HEADER, None)
                else:
                    headers.pop(common.KV_DONOR_HEADER, None)
                self.policy.pre_execute(current)
                try:
                    if splice is not None:
                        resp, replica_ok = (
                            await self._proxy_stream_attempt(
                                request, current, headers, t_arrival,
                                splice))
                    else:
                        resp, replica_ok = await self._proxy_attempt(
                            request, current, body, headers, t_arrival,
                            gen=payload is not None, tenant=tenant)
                    # Mid-stream death / a 5xx answer is delivered
                    # (can't retry) but it is still a replica failure —
                    # it must feed the breaker, not reset it.
                    if replica_ok:
                        self.breaker.record_success(current)
                    else:
                        self.breaker.record_failure(current)
                    self._finish_event(
                        req_rec,
                        'completed' if replica_ok else 'failed',
                        splice)
                    return resp
                except _ReplicaSaturated as e:
                    # Overload is not death: release (never fail) the
                    # breaker and route around it.
                    self.breaker.release(current)
                    tried.add(current)
                    saturated, last_cause = e, None
                    url = self._next_url(tried, affinity, t_deadline,
                                         headers, chain)
                    if url is not None:
                        self._requests_retried += 1
                        logger.info(
                            'replica %s shed with %d; rerouting to %s',
                            current, e.status, url)
                except _QuarantineCut:
                    # The replica was QUARANTINED under this stream.
                    # Integrity's verdict, not an availability event:
                    # release (never fail) the breaker — the replica is
                    # already leaving via drain-and-replace — and
                    # resume the stream on a healthy peer.
                    self.breaker.release(current)
                    tried.add(current)
                    last_cause, saturated = None, None
                    url = await self._next_url_or_wake(
                        tried, affinity, t_deadline, headers, splice,
                        chain)
                    if url is not None:
                        if (splice.resp is not None
                                or splice.delivered or splice.resumes):
                            splice.resumes += 1
                            self._requests_resumed += 1
                        else:
                            self._requests_retried += 1
                        logger.warning(
                            'replica %s quarantined under stream '
                            '(%d delivered tokens); resuming on %s',
                            current, len(splice.delivered), url)
                except _PreStreamFailure as e:
                    self.breaker.record_failure(current)
                    tried.add(current)
                    last_cause, saturated = e.cause, None
                    url = await self._next_url_or_wake(
                        tried, affinity, t_deadline, headers, splice,
                        chain)
                    if url is not None:
                        self._requests_retried += 1
                        logger.warning(
                            'replica %s failed pre-stream (%s); '
                            'retrying on %s', current,
                            type(e.cause).__name__, url)
                except _UpstreamDead as e:
                    self.breaker.record_failure(current)
                    tried.add(current)
                    last_cause, saturated = e.cause, None
                    url = await self._next_url_or_wake(
                        tried, affinity, t_deadline, headers, splice,
                        chain)
                    if url is not None:
                        if (splice.resp is not None
                                or splice.delivered or splice.resumes):
                            # Mid-stream: the next leg continues from
                            # the delivered tokens (resume_from).
                            splice.resumes += 1
                            self._requests_resumed += 1
                            logger.warning(
                                'replica %s died mid-stream after %d '
                                'delivered tokens (%s); resuming on '
                                '%s', current, len(splice.delivered),
                                type(e.cause).__name__, url)
                        else:
                            self._requests_retried += 1
                            logger.warning(
                                'replica %s failed pre-stream (%s); '
                                'retrying on %s', current,
                                type(e.cause).__name__, url)
                except _ClientGone:
                    # Satellite fix: the CLIENT vanished — never a
                    # replica failure, on the initial and resumed legs
                    # alike. Hand back any half-open probe slot.
                    self.breaker.release(current)
                    self._finish_event(req_rec, 'disconnect', splice)
                    if splice is not None and splice.resp is not None:
                        return splice.resp
                    return web.Response(status=499)   # never reaches it
                except BaseException:
                    # Died of something that is NOT the replica's fault
                    # (task cancellation, ...): hand back any half-open
                    # probe slot _select may have consumed, or the
                    # replica stays blacklisted with probing=True
                    # forever.
                    self.breaker.release(current)
                    raise
                finally:
                    self.policy.post_execute(current)
            # Out of replicas (or out of deadline budget).
            if splice is not None and splice.resp is not None:
                # Headers are long gone: report in-band, terminate.
                self._note_failed(tenant)
                self._finish_event(req_rec, 'failed', splice)
                with contextlib.suppress(Exception):
                    await splice.resp.write(json.dumps(
                        {'error': f'all {len(tried)} replica(s) failed '
                                  f'mid-stream; giving up after '
                                  f'{len(splice.delivered)} tokens'}
                        ).encode() + b'\n')
                    await splice.resp.write_eof()
                return splice.resp
            if saturated is not None:
                # Every replica shed: relay the last 429/503 — headers
                # intact — so the client backs off instead of hammering.
                self._requests_shed += 1
                if tenant is not None:
                    self._tenant(tenant)['shed'] += 1
                self._finish_event(req_rec, 'shed', splice)
                return web.Response(
                    status=saturated.status,
                    body=saturated.body or b'',
                    headers=saturated.headers)
            if (t_deadline is not None
                    and self._clock.monotonic() >= t_deadline):
                self._note_failed(tenant)
                self._finish_event(req_rec, 'failed', splice)
                return web.Response(
                    status=504,
                    text='deadline exceeded before any replica could '
                         'serve the request\n')
            # Every ready replica failed pre-stream.
            self._note_failed(tenant)
            self._finish_event(req_rec, 'failed', splice)
            cause = last_cause
            return web.Response(
                status=502,
                text=f'All {len(tried)} ready replica(s) failed: '
                     f'{type(cause).__name__}: {cause}\n')
        finally:
            self._inflight -= 1

    # -- lifecycle ---------------------------------------------------------
    async def bootstrap_from_state(self) -> None:
        """Crash-restart rebuild (docs/robustness.md "Crash safety"):
        repopulate the ready-replica set, the policy's affinity ring,
        and the per-replica breaker map from the serve state DB BEFORE
        the listener accepts a byte — a restarted LB must not answer
        its first requests blind (503 "no ready replicas" on a fleet
        that is perfectly healthy). One sync tick IS the rebuild: the
        ready set and replica info come straight from ``serve_state``,
        the cache-aware ring re-derives from the ready URLs, and every
        breaker re-enters closed — the correct prior for replicas the
        state DB still calls READY (a corpse re-trips within
        ``failure_threshold`` requests)."""
        await self._sync_once()

    def make_app(self) -> web.Application:
        app = web.Application()
        app.router.add_route('*', '/{tail:.*}', self.handle)
        return app

    def stop(self) -> None:
        """Request shutdown: wakes run() out of its idle wait
        immediately (thread-safe — the controller thread calls this
        when its own loop exits)."""
        self._running = False
        loop, event = self._loop, self._stop_event
        if loop is not None and event is not None:
            try:
                loop.call_soon_threadsafe(event.set)
            except RuntimeError:
                pass   # loop already closed: run() is past the wait

    async def run(self, host: str, port: int,
                  ssl_context=None) -> None:
        self._session = aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=600))
        # Rebuild before listening: a crash-restarted LB serves its
        # first request against the state DB's replica set, never an
        # empty one.
        await self.bootstrap_from_state()
        runner = web.AppRunner(self.make_app())
        await runner.setup()
        site = web.TCPSite(runner, host, port, ssl_context=ssl_context)
        await site.start()
        logger.info('service %s: load balancer on %s://%s:%d',
                    self.service_name,
                    'https' if ssl_context else 'http', host, port)
        self._loop = asyncio.get_running_loop()
        self._stop_event = asyncio.Event()
        tasks = [asyncio.create_task(self._sync_loop()),
                 asyncio.create_task(self._stats_loop())]
        try:
            # Event-driven idle: stop() ends the LB the moment it is
            # called instead of after a 0.2s poll interval (and the
            # loop no longer wakes 5x/s for nothing).
            while self._running:
                await self._stop_event.wait()
                self._stop_event.clear()
        finally:
            for t in tasks:
                t.cancel()
            await self._session.close()
            await runner.cleanup()


def run_load_balancer(service_name: str, policy_name: str, host: str,
                      port: int, ssl_context=None) -> None:
    """Blocking entry (reference run_load_balancer :289)."""
    lb = LoadBalancer(service_name, policy_name)
    asyncio.run(lb.run(host, port, ssl_context=ssl_context))
