"""Modeled replicas: a REAL engine scheduler fronting virtual slots.

Each modeled replica embeds a real ``infer/sched`` policy instance
(fcfs / EDF / wfq — the exact admission, quota, and ordering code the
production step loop drives), so fleet-scale gates prove the REAL
per-tenant shed and starvation behavior. Only the device is modeled:
decode advances one token per slot per virtual step, and the step
cadence follows an ITL-vs-concurrency curve (``PerfModel.default``:
invented, not a chip's) — so queueing, batching pressure, and
admission interact with arrival shapes the way the real engine's do.

Failure surface (what the scenarios drive):

- ``kill()`` — hard preemption: every in-flight stream dies mid-line
  (the LB's resume splice heals it);
- ``drain_flush()`` — the planned handoff: stop admitting, finish all
  in-flight work at the drain instant (the twin models drain latency
  as an atomic flush — ORDERING is what it proves: DRAINING before
  teardown, ready-set removal before death, zero client errors);
- ``wedged`` — answers probes but fails requests (breaker-flap food);
- ``slow_factor`` — brownout: steps stretch, tails grow, probes pass;
- ``poison(flavor)`` — silent data corruption (docs/robustness.md
  "Data integrity"): ``token_flip`` serves deterministically WRONG
  tokens for short prompts (address-localized corruption — the golden
  probe's tiny prompt hits it, long tenant prompts do not), ``nan``
  models a sentinel trip (in-flight streams die, new submits shed a
  503 with the ``quarantined`` marker). Probes pass either way — only
  the integrity plane can tell a poisoned replica from a healthy one.
"""
from __future__ import annotations

import dataclasses
import json
import math
import zlib
from typing import Any, Callable, Dict, List, Optional, Tuple

from skypilot_tpu.infer import sched as sched_lib
from skypilot_tpu.sim import kernel as kernel_lib
from skypilot_tpu.utils import prefix_hash


class ReplicaShed(Exception):
    """The modeled replica refused the request (429 admission-full
    from the REAL scheduler's quota logic, or 503 while draining)."""

    def __init__(self, status: int, message: str,
                 retry_after_s: float) -> None:
        super().__init__(message)
        self.status = status
        self.retry_after_s = retry_after_s


class ReplicaQuarantined(ReplicaShed):
    """503 from a sentinel-tripped replica: the body carries the
    ``quarantined`` reason marker — the twin's mirror of the infer
    server's corrupt-health contract (503 + ``Retry-After`` +
    ``{'error': 'replica corrupt', 'quarantined': true}``). The LB
    releases (never breaker-fails) it, exactly like a drain 503."""

    def __init__(self) -> None:
        super().__init__(
            503, json.dumps({'error': 'replica corrupt',
                             'quarantined': True}),
            retry_after_s=1.0)


# Address-locality bound of the token_flip corruption model: only
# prompts at most this many tokens long hit the corrupt rows (a bad
# HBM bank corrupts SOME addresses, not the whole model — modeled as
# the embedding rows the golden probe's tiny prompt touches). Long
# tenant prompts decode correctly, which is exactly what makes the
# corruption SILENT to every liveness signal and non-vacuous for the
# probe plane to catch.
CORRUPT_SHORT_PROMPT_MAX = 6

# Bumped when the sim oracle's token function changes — the golden
# fixture fingerprint (observability/integrity.py) is minted against
# it, and a mismatch must fail loudly at probe-arm time.
ORACLE_VERSION = 1


def oracle_fingerprint() -> str:
    """The sim oracle's identity string — what a golden fixture for
    model key ``'sim'`` must have been minted against."""
    return f'sim-greedy-v{ORACLE_VERSION}'


@dataclasses.dataclass
class PerfModel:
    """The modeled device: virtual step time as a function of decode
    concurrency (piecewise-linear between the curve's points), plus
    the prefill budget per step that sets modeled TTFT."""

    # (concurrency, step_seconds), ascending concurrency.
    itl_curve: List[Tuple[float, float]]
    prefill_tokens_per_step: float = 256.0
    # Uniform stretch: a scenario slows or speeds every step without
    # changing the curve's SHAPE.
    scale: float = 1.0

    def step_s(self, concurrency: int) -> float:
        c = max(1.0, float(concurrency))
        curve = self.itl_curve
        if c <= curve[0][0]:
            base = curve[0][1]
        elif c >= curve[-1][0]:
            base = curve[-1][1]
        else:
            base = curve[-1][1]
            for (ca, sa), (cb, sb) in zip(curve, curve[1:]):
                if c < cb:
                    base = sa + (sb - sa) * (c - ca) / (cb - ca)
                    break
        return base * self.scale

    @classmethod
    def default(cls, scale: float = 1.0) -> 'PerfModel':
        """The twin's step-time curve. The numbers are INVENTED (a
        plausible shape: a step slows as more requests share it), and
        every gate of ``tests/sim`` is tuned to them, so they stay as
        they are. They are not a chip's: the benchmark's chat cell
        reads 19.98 ms a decode step at four requests in flight
        (``itl_p50_ms``; ledger, PR 28). The twin proves ordering and
        control-loop behaviour in virtual time, never speed."""
        return cls(itl_curve=[(1, 0.020), (8, 0.030), (16, 0.045)],
                   scale=scale)


class _Req:
    """The request object handed to the REAL scheduler: exactly the
    attribute surface ``infer/sched`` relies on (tenant, prompt and
    output token lists for ``request_cost``, cancelled/deadline for
    sweeps, submitted_at for victim choice)."""

    __slots__ = ('tenant', 'prompt_tokens', 'output_tokens',
                 'cancelled', 'deadline', 'submitted_at',
                 'max_new_tokens', 'resume_len', 'stream',
                 'submit_step', 'first_token_step', 'prefill_left',
                 'dispatched_at', 'prompt_key', 'chain')

    def __init__(self, tenant: str, prompt_tokens: List[int],
                 max_new_tokens: int, resume_from: List[int],
                 submitted_at: float, submit_step: int,
                 prefill_left: int) -> None:
        self.tenant = tenant
        self.prompt_tokens = list(prompt_tokens)
        # Resume tokens pre-seed the output exactly like the engine's
        # resume_from splice path: they count toward request_cost (the
        # re-prefill the scheduler charges) and are never re-emitted.
        self.output_tokens: List[int] = list(resume_from)
        self.cancelled = False
        self.deadline: Optional[float] = None
        self.submitted_at = submitted_at
        self.max_new_tokens = max_new_tokens
        self.resume_len = len(resume_from)
        self.stream = SimStream()
        self.submit_step = submit_step
        self.first_token_step: Optional[int] = None
        self.prefill_left = prefill_left
        self.dispatched_at: Optional[float] = None
        # Chained page hashes of the prompt (fleet KV index key
        # space); empty when the replica's KV modeling is unarmed.
        self.chain: List[int] = []
        # The whole greedy continuation is a pure function of the
        # prompt (deterministic resume bit-identity); hash it once.
        self.prompt_key = zlib.crc32(
            json.dumps(self.prompt_tokens).encode())


class SimStream:
    """The virtual wire between a modeled replica and one LB proxy
    leg: the replica pushes ``('line', dict)`` events, the transport
    awaits them; ``('dead', None)`` models the connection dying with
    the replica."""

    __slots__ = ('_buf', '_waiter', '_dead')

    def __init__(self) -> None:
        self._buf: List[Tuple[str, Any]] = []
        self._waiter: Optional[kernel_lib.SimFuture] = None
        self._dead = False

    def push_line(self, obj: Dict[str, Any]) -> None:
        self._push(('line', obj))

    def fail(self) -> None:
        self._dead = True
        self._push(('dead', None))

    def _push(self, event: Tuple[str, Any]) -> None:
        waiter, self._waiter = self._waiter, None
        if waiter is not None:
            waiter.set_result(event)
        else:
            self._buf.append(event)

    def next_event(self) -> kernel_lib.SimFuture:
        fut = kernel_lib.SimFuture()
        if self._buf:
            fut.set_result(self._buf.pop(0))
        elif self._dead:
            fut.set_result(('dead', None))
        else:
            if self._waiter is not None:
                raise RuntimeError('one consumer per stream')
            self._waiter = fut
        return fut


def expected_continuation(prompt_tokens: List[int],
                          n: int) -> List[int]:
    """The exact token ids an UNKILLED run of this prompt produces —
    the oracle the twin audits every delivered stream against (a
    resumed/spliced stream must match it byte for byte)."""
    key = zlib.crc32(
        json.dumps([int(t) for t in prompt_tokens]).encode())
    return [_token(key, i) for i in range(n)]


def _token(prompt_key: int, index: int) -> int:
    """Deterministic, process-stable token id (NEVER builtin hash():
    PYTHONHASHSEED would break the cross-run byte-identity gate). A
    killed-and-resumed request regenerates the exact continuation, so
    the LB's splice is bit-identical to an unkilled run — same
    contract the real engine's greedy resume provides."""
    return 2 + (zlib.crc32(f'{prompt_key}/{index}'.encode())
                % 200)


class ModelReplica:
    """One modeled serving replica on the virtual transport."""

    # Modeled radix index bound (mirrors the engine's bounded wire
    # summary): oldest chains evict first, journaled as removals so
    # the LB's delta mirror tracks them.
    MAX_KV_HASHES = 8192
    _KV_JOURNAL_KEEP = 1024
    _KV_WINDOW = 256

    def __init__(self, kern: kernel_lib.Kernel, url: str, *,
                 scheduler: str = 'fcfs',
                 sched_config: Optional[sched_lib.SchedulerConfig] = None,
                 slots: int = 8,
                 perf: Optional[PerfModel] = None,
                 on_request_done: Optional[Callable[..., None]] = None,
                 role: str = 'mixed',
                 kv_page: int = 0,
                 kv_ttl_s: float = 0.0,
                 kv_bytes_per_token: int = 65536,
                 kv_pull: Optional[Callable[[str], Any]] = None,
                 transfer_s: Optional[Callable[[int], float]] = None,
                 kv_stats: Optional[Dict[str, int]] = None,
                 on_kv_event: Optional[Callable[..., None]] = None
                 ) -> None:
        self.kernel = kern
        self.url = url
        self.sched = sched_lib.make(scheduler, sched_config)
        self.slots = slots
        self.perf = perf or PerfModel.default()
        self.on_request_done = on_request_done
        self.alive = True
        self.draining = False
        self.wedged = False
        self.slow_factor = 1.0
        self.corrupt_flavor: Optional[str] = None
        self.active: List[_Req] = []
        self.steps = 0
        self.decode_tokens = 0
        self._step_scheduled = False
        # Disaggregated prefill/decode modeling (docs/serving.md):
        # ``kv_page`` 0 keeps the whole plane inert — pre-existing
        # scenarios replay byte-identically. The modeled radix index
        # lives in the SAME chained-hash key space as real engines
        # (utils/prefix_hash.py), so the REAL FleetPrefixIndex folds
        # it without knowing it is modeled.
        self.role = role
        self.kv_page = int(kv_page)
        # Idle TTL — the model of decode-page-pressure eviction: a
        # prefix nobody re-touches for ``kv_ttl_s`` virtual seconds is
        # gone (LRU under allocator pressure, abstracted to idle
        # lifetime). 0 = never expires.
        self.kv_ttl_s = float(kv_ttl_s)
        self.kv_bytes_per_token = int(kv_bytes_per_token)
        self.kv_pull = kv_pull
        self.transfer_s = transfer_s
        self.kv_stats = kv_stats
        self.on_kv_event = on_kv_event
        # hash -> last-touch virtual time (insertion-ordered) + the
        # (gen, op, hash) journal build_snapshot delta-encodes from.
        self.kv_hashes: Dict[int, float] = {}
        self.kv_gen = 0
        self.kv_journal: List[Tuple[int, str, int]] = []
        self.kv_transfers = 0
        self.kv_transfer_bytes = 0
        self.kv_transfer_failures = 0
        self.kv_transfer_durs: List[float] = []
        self._kv_pending: List[_Req] = []

    # ---- ingress ---------------------------------------------------------
    def submit(self, payload: Dict[str, Any], tenant: str,
               resume_from: List[int],
               donor: Optional[str] = None) -> SimStream:
        if not self.alive:
            raise ConnectionError(f'{self.url} is dead')
        now = self.kernel.now
        if self.corrupt_flavor == 'nan':
            # The on-device sentinel tripped: the server's admission
            # edge sheds everything with the quarantined marker
            # (mirroring infer/server._admit_generate's corrupt 503)
            # until the control plane replaces the replica.
            raise ReplicaQuarantined()
        if self.draining:
            raise ReplicaShed(503, 'draining', retry_after_s=1.0)
        prompt = [int(t) for t in payload.get('tokens') or []]
        max_new = int(payload.get('max_new_tokens') or 8)
        prefill_left = max(1, math.ceil(
            len(prompt) / self.perf.prefill_tokens_per_step))
        req = _Req(tenant or sched_lib.DEFAULT_TENANT, prompt, max_new,
                   resume_from, now, self.steps, prefill_left)
        try:
            # THE real admission code: global bounds under fcfs/EDF,
            # weight-share quotas + tenant-scoped Retry-After under
            # wfq.
            self.sched.admit(req, drain_tps=self._drain_tps())
        except sched_lib.AdmissionError as e:
            raise ReplicaShed(429, str(e),
                              retry_after_s=e.retry_after_s) from e
        if self.kv_page and not self._kv_admit(req, donor):
            return req.stream   # enqueue deferred behind a KV pull
        self._enqueue_ready(req)
        return req.stream

    def _enqueue_ready(self, req: _Req) -> None:
        """The one enqueue edge — shared by plain admission and the
        deferred KV-pull path so the kernel thread's scheduler calls
        stay at a single audited site."""
        self.sched.enqueue(req)
        self._ensure_step()

    # ---- KV prefix tier (docs/serving.md "Disaggregated
    # prefill/decode") ----------------------------------------------------
    def _kv_stat(self, key: str, n: int = 1) -> None:
        if self.kv_stats is not None:
            self.kv_stats[key] = self.kv_stats.get(key, 0) + n

    def _kv_admit(self, req: _Req, donor: Optional[str]) -> bool:
        """Price the request's prefill against the modeled radix index
        and (when the LB named a donor holding a longer prefix) start
        the donor pull. Returns False when the enqueue is deferred
        until the transfer lands — the caller must NOT enqueue."""
        req.chain = prefix_hash.chain_hashes(req.prompt_tokens,
                                             self.kv_page)
        self._kv_stat('submits')
        self._kv_sweep()
        local = prefix_hash.match_depth(req.chain, self.kv_hashes)
        if local:
            self._kv_touch(req.chain[:local])
        if donor is not None and self.kv_pull is not None:
            dm = self.kv_pull(donor)
            d_depth = (prefix_hash.match_depth(req.chain, dm.kv_hashes)
                       if dm is not None and dm.alive else 0)
            if dm is None or not dm.alive:
                # The LB routed against a donor that died before the
                # pull: degrade to recompute, never an error.
                self.kv_transfer_failures += 1
                self._kv_stat('failures')
                self._kv_event(req, donor, ok=False, pages=0)
            elif d_depth > local:
                pages = d_depth - local
                nbytes = pages * self.kv_page * self.kv_bytes_per_token
                delay = (self.transfer_s(nbytes)
                         if self.transfer_s is not None else 0.0)
                self._kv_pending.append(req)
                self.kernel.call_later(
                    delay, self._kv_pull_done, req, donor, d_depth,
                    nbytes, delay)
                return False
        if local > 0:
            self._kv_stat('warm')
            self._kv_stat('local_warm')
        self._set_prefill(req, local)
        return True

    def _kv_pull_done(self, req: _Req, donor: str, d_depth: int,
                      nbytes: int, dur: float) -> None:
        """The deferred half of a donor pull: the transfer's virtual
        latency has elapsed — attach (donor still alive) or fall back
        to plain recompute (donor died mid-transfer)."""
        if req not in self._kv_pending:
            return   # this replica died first; the stream already failed
        self._kv_pending.remove(req)
        if not self.alive:
            return
        local = prefix_hash.match_depth(req.chain, self.kv_hashes)
        dm = self.kv_pull(donor) if self.kv_pull is not None else None
        if dm is None or not dm.alive:
            # Donor died mid-transfer: recompute from whatever the
            # local index already covers. Client-invisible by design.
            self.kv_transfer_failures += 1
            self._kv_stat('failures')
            self._kv_event(req, donor, ok=False, pages=d_depth - local)
        else:
            depth = max(local,
                        min(d_depth, prefix_hash.match_depth(
                            req.chain, dm.kv_hashes)))
            self._kv_add(req.chain[:depth])
            self.kv_transfers += 1
            self.kv_transfer_bytes += nbytes
            self.kv_transfer_durs.append(dur)
            del self.kv_transfer_durs[:-self._KV_WINDOW]
            self._kv_stat('transfers')
            self._kv_stat('transfer_bytes', nbytes)
            self._kv_stat('warm')
            self._kv_event(req, donor, ok=True, pages=depth - local)
            local = depth
        self._set_prefill(req, local)
        self._enqueue_ready(req)

    def _kv_event(self, req: _Req, donor: str, *, ok: bool,
                  pages: int) -> None:
        if self.on_kv_event is not None:
            self.on_kv_event(url=self.url, donor=donor, ok=ok,
                             pages=pages, tenant=req.tenant)

    def _set_prefill(self, req: _Req, warm_depth: int) -> None:
        """Re-price the prefill with ``warm_depth`` pages already
        attached — the boundary-only prefill that makes transfers
        faster than recompute."""
        warm = warm_depth * self.kv_page
        req.prefill_left = max(1, math.ceil(
            max(0, len(req.prompt_tokens) - warm)
            / self.perf.prefill_tokens_per_step))

    def _kv_add(self, hashes: List[int]) -> None:
        """Index chain links (journaled adds), evicting oldest past
        the bound (journaled removals) — the delta wire the REAL
        FleetPrefixIndex mirrors."""
        now = self.kernel.now
        for h in hashes:
            if h in self.kv_hashes:
                self.kv_hashes[h] = now   # refresh idle TTL
                continue
            self.kv_hashes[h] = now
            self.kv_gen += 1
            self.kv_journal.append((self.kv_gen, '+', h))
        while len(self.kv_hashes) > self.MAX_KV_HASHES:
            old = next(iter(self.kv_hashes))
            del self.kv_hashes[old]
            self.kv_gen += 1
            self.kv_journal.append((self.kv_gen, '-', old))
        del self.kv_journal[:-self._KV_JOURNAL_KEEP]

    def _kv_touch(self, hashes: List[int]) -> None:
        now = self.kernel.now
        for h in hashes:
            if h in self.kv_hashes:
                self.kv_hashes[h] = now

    def _kv_sweep(self) -> None:
        """Expire idle prefixes — the model of decode-page-pressure
        eviction (an untouched prefix loses its pages to the
        allocator). Journaled like any other removal so the LB mirror
        converges through the same delta wire."""
        if self.kv_ttl_s <= 0.0 or not self.kv_hashes:
            return
        cutoff = self.kernel.now - self.kv_ttl_s
        dead = [h for h, t in self.kv_hashes.items() if t < cutoff]
        for h in dead:
            del self.kv_hashes[h]
            self.kv_gen += 1
            self.kv_journal.append((self.kv_gen, '-', h))
        del self.kv_journal[:-self._KV_JOURNAL_KEEP]

    def _drain_tps(self) -> float:
        if not self.steps:
            return 0.0
        return self.decode_tokens / max(
            1e-9, self.steps * self.perf.step_s(self.slots))

    # ---- the virtual step loop -------------------------------------------
    def _ensure_step(self) -> None:
        if (self._step_scheduled or not self.alive
                or (not self.active and not self.sched.pending())):
            return
        self._step_scheduled = True
        delay = self.perf.step_s(max(1, len(self.active))) \
            * self.slow_factor
        self.kernel.call_later(delay, self._step)

    def _step(self) -> None:
        self._step_scheduled = False
        if not self.alive:
            return
        self.steps += 1
        now = self.kernel.now
        # Slot refill through the real policy (wfq rotates tenants,
        # EDF picks the most urgent, fcfs pops FIFO).
        while len(self.active) < self.slots:
            req = self.sched.pop_next()
            if req is None:
                break
            req.dispatched_at = now
            self.sched.note_queue_wait(req, now - req.submitted_at)
            self.active.append(req)
        for req in list(self.active):
            if len(req.output_tokens) >= req.max_new_tokens:
                # A resume leg whose boundary already covers the whole
                # budget (the kill landed after the last token but
                # before the done line): only the done line is owed.
                self._finish(req, 'length')
                continue
            if req.prefill_left > 0:
                req.prefill_left -= 1
                if req.prefill_left == 0 and req.chain:
                    # Prefill landed: the prompt's pages are now
                    # cached here — index the whole chain so the next
                    # sync tick advertises it fleet-wide.
                    self._kv_add(req.chain)
                continue
            self._emit_one(req)
        self._ensure_step()

    def _emit_one(self, req: _Req) -> None:
        idx = len(req.output_tokens)
        tok = _token(req.prompt_key, idx)
        if (self.corrupt_flavor == 'token_flip'
                and len(req.prompt_tokens) <= CORRUPT_SHORT_PROMPT_MAX):
            # Silent corruption: a deterministically WRONG token (the
            # oracle never emits it for this position), only on
            # prompts short enough to hit the corrupt addresses.
            tok += 1
        req.output_tokens.append(tok)
        self.decode_tokens += 1
        self.sched.note_tokens(req, 1)
        if req.first_token_step is None:
            req.first_token_step = self.steps
            self.sched.note_first_token(
                req, self.kernel.now - req.submitted_at)
        # Only post-resume-boundary tokens go on the wire (the engine's
        # resume contract — the LB already delivered the rest); the
        # budget is TOTAL output across legs, so the spliced stream
        # carries exactly max_new_tokens like an unkilled run.
        req.stream.push_line({'tokens': [tok]})
        if len(req.output_tokens) >= req.max_new_tokens:
            self._finish(req, 'length')

    def _finish(self, req: _Req, reason: str) -> None:
        self.active.remove(req)
        waited = ((req.first_token_step or self.steps)
                  - req.submit_step)
        req.stream.push_line({
            'done': True, 'finish_reason': reason,
            'queue_wait_s': round(
                (req.dispatched_at or req.submitted_at)
                - req.submitted_at, 6),
            # Scheduler-virtual fairness clock (the starvation gates
            # assert on this, not wall time — the PR 11 rule).
            'steps_waited': waited,
        })
        if self.on_request_done is not None:
            self.on_request_done(self.url, req, reason)

    # ---- failure surface -------------------------------------------------
    def _fail_all_streams(self) -> None:
        """Fail every admitted stream — active and queued — at this
        instant. Shared by kill (power loss) and poison('nan') (the
        sentinel sheds the whole batch); the LB resume splice is what
        heals the clients either way."""
        for req in self.active:
            req.stream.fail()
        self.active.clear()
        # Requests parked behind an in-flight KV pull die with the
        # replica too (their enqueue never happened).
        for req in self._kv_pending:
            req.stream.fail()
        self._kv_pending.clear()
        while True:
            req = self.sched.pop_next()
            if req is None:
                break
            req.stream.fail()

    def kill(self) -> None:
        """Hard death (spot reclaim without notice, zone outage):
        every in-flight and queued stream dies mid-flight; the LB's
        resume path is what heals the clients."""
        if not self.alive:
            return
        self.alive = False
        self._fail_all_streams()

    def poison(self, flavor: str) -> None:
        """Silent data corruption onset (bad HBM bank, flaky chip).

        ``token_flip``: the replica keeps serving but emits WRONG
        tokens for short prompts (address-localized corruption) — the
        liveness probe still passes; only the golden-probe canary's
        byte compare can see it. ``nan``: the on-device sentinel
        trips — in-flight streams die (their clients heal through the
        LB resume splice), and every new submit sheds 503 with the
        quarantined marker; the HTTP surface stays up (alive=True) so
        death-detection never fires — quarantine must come from the
        integrity plane, not the breaker."""
        if flavor not in ('token_flip', 'nan'):
            raise ValueError(f'unknown corruption flavor {flavor!r}')
        self.corrupt_flavor = flavor
        if flavor == 'nan':
            self._fail_all_streams()

    def drain_flush(self) -> None:
        """The planned handoff: stop admitting (new requests shed 503
        and reroute), then finish EVERY admitted request — active and
        queued — at the drain instant. Latency of the drain itself is
        modeled as atomic; what the twin proves is the ordering
        contract (drain before teardown ⇒ zero client-visible
        errors)."""
        self.draining = True
        while True:
            req = self.sched.pop_next()
            if req is None:
                break
            req.dispatched_at = req.dispatched_at or self.kernel.now
            self.active.append(req)
        for req in list(self.active):
            req.prefill_left = 0
            while len(req.output_tokens) < req.max_new_tokens:
                self._emit_one(req)
            if req in self.active:    # boundary-covered resume leg
                self._finish(req, 'length')

    # ---- observability (the LB's /metrics fetch) -------------------------
    def metrics_row(self, since_gen: Optional[int] = None
                    ) -> Tuple[str, int, Dict[str, Any]]:
        """The ``(url, num_waiting, eff)`` row the LB sync tick
        ingests — same keys the real ``/metrics`` fetch extracts.
        ``since_gen`` (the LB mirror's generation) asks for the
        delta-encoded radix summary, exactly like the real fetch's
        ``?prefix_gen=`` query."""
        tps = (round(self.decode_tokens / self.steps, 4)
               if self.steps else None)
        eff = {'decode_tokens': self.decode_tokens}
        if tps is not None:
            eff['tokens_per_step'] = tps
        if self.kv_page:
            self._kv_sweep()
            durs = sorted(self.kv_transfer_durs)
            eff['kv_transfers_total'] = self.kv_transfers
            eff['kv_transfer_bytes'] = self.kv_transfer_bytes
            eff['kv_transfer_failures'] = self.kv_transfer_failures
            if durs:
                eff['kv_transfer_p99_s'] = round(
                    durs[min(len(durs) - 1, int(len(durs) * 0.99))], 6)
            eff['role'] = self.role
            if since_gen is not None:
                eff['kv_prefix_index'] = prefix_hash.build_snapshot(
                    self.kv_gen,
                    prefix_hash.fold_crc(self.kv_hashes),
                    self.kv_page, self.kv_journal, self.kv_hashes,
                    since_gen)
        return self.url, self.sched.pending(), eff
