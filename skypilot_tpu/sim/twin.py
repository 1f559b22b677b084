"""The digital twin orchestrator: one scenario → one deterministic
replay → one report.

Wiring (all REAL control-plane code, only the edges virtualized):

- a scratch ``SKY_TPU_HOME`` holds the run's serve state DB (fresh per
  run, so sqlite AUTOINCREMENT ids — which appear in the decision
  log — are identical across same-seed runs);
- the kernel's :class:`~skypilot_tpu.utils.vclock.VirtualClock` is
  installed process-wide for the replay, so every ``vclock`` read in
  ``serve/`` observes virtual time;
- the REAL :class:`ServeController` ticks at the scenario cadence
  (launch/terminate through the REAL ``ReplicaManager`` over the
  virtual cloud), the REAL LB syncs/flushes at its cadences, and
  every trace event becomes a REAL ``LoadBalancer.handle`` coroutine
  on the kernel trampoline;
- the decision log records every launch (with placement), terminate,
  drain, preemption notice, reclaim kill, autoscaler target change,
  and per-request outcome, stamped with virtual time + sequence.
  ``SimReport.decision_log_jsonl()`` is the byte-identity surface the
  determinism gate hashes.
"""
from __future__ import annotations

import json
import logging
import os
import random
import shutil
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

import yaml

from skypilot_tpu.infer import sched as sched_lib
from skypilot_tpu.observability import integrity
from skypilot_tpu.serve import controller as controller_lib
from skypilot_tpu.serve import state as serve_state
from skypilot_tpu.serve.state import ReplicaStatus
from skypilot_tpu.sim import cloud as cloud_lib
from skypilot_tpu.sim import kernel as kernel_lib
from skypilot_tpu.sim import replica as replica_lib
from skypilot_tpu.sim import transport as transport_lib
from skypilot_tpu.sim.scenarios import Fault, KillSpec, Scenario
from skypilot_tpu.utils import common
from skypilot_tpu.utils import db as db_lib
from skypilot_tpu.utils import failpoints
from skypilot_tpu.utils import retry as retry_lib
from skypilot_tpu.utils import vclock

logger = logging.getLogger(__name__)


class SimReport:
    """Everything a gate asserts on."""

    def __init__(self, scenario: str, seed: int) -> None:
        self.scenario = scenario
        self.seed = seed
        self.decisions: List[Dict[str, Any]] = []
        self.records: List[Dict[str, Any]] = []
        self.lb_metrics: Dict[str, Any] = {}
        # VirtualCloud billing totals (market scenarios): what the
        # $-saved-at-SLO gate compares across runs.
        self.cost: Dict[str, Any] = {}
        # KV prefix tier rollup (disagg scenarios): fleet-wide
        # submit/warm/transfer/failure counters from the modeled
        # replicas — the hit-rate and fallback gates assert on these.
        self.kv: Dict[str, Any] = {}
        # End-of-replay control-plane convergence view (captured before
        # the scratch home is torn down): the crash gates compare a
        # killed run's final fleet against the unkilled baseline's.
        self.final_fleet: Dict[str, Any] = {}
        self.wall_s = 0.0
        self.events_run = 0

    # ---- rollups -------------------------------------------------------
    def _count(self, kind: str) -> int:
        return sum(1 for d in self.decisions if d['kind'] == kind)

    @property
    def launches(self) -> int:
        return self._count('launch')

    @property
    def drains(self) -> int:
        return self._count('drain')

    @property
    def reclaim_kills(self) -> int:
        return self._count('reclaim_kill')

    @property
    def preemption_notices(self) -> int:
        return self._count('preemption_notice')

    @property
    def scale_targets(self) -> List[int]:
        return [d['target'] for d in self.decisions
                if d['kind'] == 'scale_target']

    @property
    def completed(self) -> int:
        return sum(1 for r in self.records if r['completed'])

    @property
    def shed(self) -> int:
        return sum(1 for r in self.records if r['shed'])

    @property
    def resumed_requests(self) -> int:
        return sum(1 for r in self.records if r.get('resumed'))

    @property
    def crashes(self) -> int:
        return self._count('crash')

    @property
    def recoveries(self) -> List[Dict[str, Any]]:
        """The 'recover' decisions — one per controller restart, with
        the reconcile report rollup and the idempotence verdict."""
        return [d for d in self.decisions if d['kind'] == 'recover']

    @property
    def client_retries(self) -> int:
        """Streams severed by an LB kill and retried (with resume_from)
        against the restarted LB."""
        return sum(int(r.get('lb_retries') or 0) for r in self.records)

    @property
    def slo_alerts(self) -> List[Dict[str, Any]]:
        """Alert transitions from the REAL burn-rate evaluator
        (docs/observability.md "SLOs and alerting"); the fidelity
        gates assert on these."""
        return [d for d in self.decisions if d['kind'] == 'slo_alert']

    def slo_log_jsonl(self) -> str:
        """The alert decision log alone, one JSON line per
        transition — byte-identical across same-seed runs."""
        return '\n'.join(json.dumps(d, sort_keys=True)
                         for d in self.slo_alerts)

    @property
    def placements(self) -> List[Dict[str, Any]]:
        """The FleetPlacer's per-tick decisions (cost-optimized
        scenarios only; docs/cost.md)."""
        return [d for d in self.decisions if d['kind'] == 'place']

    def placement_log_jsonl(self) -> str:
        """The placer decision log alone — the cost gate's
        byte-identity surface (same seed ⇒ identical string)."""
        return '\n'.join(json.dumps(d, sort_keys=True)
                         for d in self.placements)

    @property
    def client_errors(self) -> List[Dict[str, Any]]:
        """Client-visible failures: anything that neither completed
        nor was an orderly admission shed (the zero-errors gates
        assert this list is empty)."""
        return [r for r in self.records
                if not r['completed'] and not r['shed']]

    def tenant_summary(self) -> Dict[str, Dict[str, Any]]:
        from tests.load_tests import loadgen
        return loadgen.tenant_summary(self.records)

    def decision_log_jsonl(self) -> str:
        """The byte-identity surface: same seed ⇒ identical string."""
        return '\n'.join(
            json.dumps(d, sort_keys=True) for d in self.decisions)

    def summary(self) -> Dict[str, Any]:
        return {
            'scenario': self.scenario, 'seed': self.seed,
            'virtual_events': self.events_run,
            'wall_s': round(self.wall_s, 3),
            'requests': len(self.records),
            'completed': self.completed, 'shed': self.shed,
            'client_errors': len(self.client_errors),
            'resumed_requests': self.resumed_requests,
            'launches': self.launches, 'drains': self.drains,
            'preemption_notices': self.preemption_notices,
            'reclaim_kills': self.reclaim_kills,
            'crashes': self.crashes,
            'client_retries': self.client_retries,
            'final_fleet': self.final_fleet,
            'scale_targets': self.scale_targets,
            'placements': len(self.placements),
            'cost': self.cost,
            'kv': self.kv,
            'fleet_prefix_hit_rate': self.lb_metrics.get(
                'fleet_prefix_hit_rate'),
            'cold_starts': self.lb_metrics.get('cold_starts_total'),
            'ready_replicas': self.lb_metrics.get('ready_replicas'),
            'lb_ttft_p50_s': self.lb_metrics.get('ttft_p50_s'),
            'lb_ttft_p99_s': self.lb_metrics.get('ttft_p99_s'),
        }


class _ClientCall:
    """One logical client request across LB crash-restarts: a severed
    leg's delivered tokens become the next leg's ``resume_from`` (the
    SDK-visible half of PR 5's resume splice)."""

    __slots__ = ('ev', 't0', 'resume', 'retries', 'req', 'fut')

    def __init__(self, ev, t0: float) -> None:
        self.ev = ev
        self.t0 = t0
        self.resume: List[int] = []
        self.retries = 0
        self.req: Optional[transport_lib.SimRequest] = None
        self.fut: Optional[kernel_lib.SimFuture] = None


class DigitalTwin:
    """One replay of one scenario at one seed. ``kill`` injects an
    extra :class:`KillSpec` on top of the scenario's own (the
    kill-anywhere sweep's per-boundary knob)."""

    SERVICE = 'twin'

    def __init__(self, scenario: Scenario, seed: int = 0, *,
                 keep_home: bool = False,
                 kill: Optional[KillSpec] = None) -> None:
        self.sc = scenario
        self.seed = seed
        self.keep_home = keep_home
        self.kernel = kernel_lib.Kernel()
        self.report = SimReport(scenario.name, seed)
        self._perf = self._make_perf()
        self._cloud: Optional[cloud_lib.VirtualCloud] = None
        self._lb: Optional[transport_lib.TwinLoadBalancer] = None
        self._controller = None
        self._executor: Optional[cloud_lib.SimExecutor] = None
        # Kill-anywhere machinery (docs/robustness.md "Crash safety").
        self.kills: List[KillSpec] = list(scenario.kills)
        if kill is not None:
            self.kills.append(kill)
        self._kills_fired: set = set()
        # Armed between a controller kill and its restart: the next
        # VirtualCloud crash-window gate tears the op on the stack
        # (slice created / drain done, DB not yet written).
        self._crash_armed = False
        # In-flight logical client calls (insertion-ordered — the kill
        # handler's severing order is deterministic) and legs parked
        # while the LB is dead.
        self._inflight_calls: Dict[int, _ClientCall] = {}
        self._pending_legs: List[_ClientCall] = []
        # Disagg role carving: launch-order-deterministic, so the
        # prefill/decode split is identical across same-seed runs.
        self._replicas_made = 0
        self._prefill_made = 0
        self._kv_stats: Dict[str, int] = {}
        # One-shot donor trap (the 'donor_reclaim' fault): the next
        # donor pull after arming gets its donor hard-killed
        # mid-transfer — the deterministic worst-case race the
        # recompute fallback exists for.
        self._donor_trap = False

    # ---- pieces --------------------------------------------------------
    def _make_perf(self) -> replica_lib.PerfModel:
        perf = replica_lib.PerfModel.default(scale=self.sc.perf_scale)
        if self.sc.prefill_tokens_per_step is not None:
            perf.prefill_tokens_per_step = float(
                self.sc.prefill_tokens_per_step)
        return perf

    def _log(self, kind: str, **fields: Any) -> None:
        self.report.decisions.append(
            {'t': round(self.kernel.now, 6),
             'seq': len(self.report.decisions), 'kind': kind,
             **fields})
        # Kill-anywhere boundary injection: a KillSpec armed at this
        # decision's seq fires the virtual kill -9 the instant the
        # decision lands — if the decision was logged from inside a
        # cloud-facing op (launch/drain/terminate), the crash gate
        # tears that op at its real crash window before it can write
        # the DB.
        seq = len(self.report.decisions) - 1
        for i, k in enumerate(self.kills):
            if (k.at_seq is not None and k.at_seq == seq
                    and i not in self._kills_fired):
                self._kills_fired.add(i)
                self._kill(k.target, k.restart_delay_s)

    def _make_replica(self, url: str) -> replica_lib.ModelReplica:
        cfg = sched_lib.SchedulerConfig(
            max_queue_requests=self.sc.max_queue_requests,
            max_queue_tokens=self.sc.max_queue_tokens,
            tenant_weights=self.sc.tenant_weights)
        kw: Dict[str, Any] = {}
        if self.sc.kv_page:
            # Role carve by launch order: keep the prefill pool at
            # ``prefill_fraction`` of the fleet as launches accrue.
            self._replicas_made += 1
            role = 'mixed'
            if (self.sc.prefill_fraction > 0
                    and self._prefill_made < self.sc.prefill_fraction
                    * self._replicas_made):
                self._prefill_made += 1
                role = 'prefill'
            kw = {
                'role': role, 'kv_page': self.sc.kv_page,
                'kv_ttl_s': self.sc.kv_ttl_s,
                'kv_bytes_per_token': self.sc.kv_bytes_per_token,
                'kv_pull': self._kv_donor_model,
                'transfer_s': self._cloud.kv_transfer_s,
                'kv_stats': self._kv_stats,
                'on_kv_event': self._on_kv_transfer,
            }
        return replica_lib.ModelReplica(
            self.kernel, url, scheduler=self.sc.scheduler,
            sched_config=cfg, slots=self.sc.slots, perf=self._perf,
            **kw)

    def _kv_donor_model(self, url: str):
        """Donor resolver for modeled pulls: the donor's model while
        its slice is still alive (a reclaimed donor resolves to a
        dead model — the recompute-fallback path). An armed
        ``donor_reclaim`` trap reclaims the donor's slice halfway
        through the transfer floor — the pull was admitted against a
        live donor and completes against a dead one."""
        model = self._model_by_url(url)
        if self._donor_trap and model is not None and model.alive:
            cluster = next(
                (k for k in sorted(self._cloud.slices)
                 if self._cloud.slices[k].url == url
                 and self._cloud.slices[k].alive), None)
            if cluster is not None:
                self._donor_trap = False
                self.kernel.call_later(
                    self.sc.kv_transfer_floor_s * 0.5,
                    self._cloud.hard_kill, cluster)
        return model

    def _on_kv_transfer(self, **fields: Any) -> None:
        """Every modeled KV transfer outcome lands in the decision
        log (the byte-identity surface) — the disagg gates assert
        transfer and fallback counts from here too."""
        self._log('kv_transfer', **fields)

    def _model_by_url(self, url: str):
        s = self._cloud.by_url.get(url)
        return s.model if s is not None else None

    def _service_config(self) -> Dict[str, Any]:
        sc = self.sc
        floor = (sc.replicas if sc.min_replicas is None
                 else sc.min_replicas)
        policy: Dict[str, Any] = {'min_replicas': floor}
        if sc.max_replicas is not None:
            policy['max_replicas'] = sc.max_replicas
        if sc.queue_length_threshold is not None:
            policy['queue_length_threshold'] = sc.queue_length_threshold
        policy['upscale_delay_seconds'] = sc.upscale_delay_s
        policy['downscale_delay_seconds'] = sc.downscale_delay_s
        # Cost plane + scale-to-zero (docs/cost.md): the REAL spec
        # validation sees these — a scenario declaring min_replicas 0
        # without a wake policy fails exactly like a user task would.
        if sc.cost_optimized:
            policy['cost_optimized'] = True
            policy['relaunch_overhead_seconds'] = sc.relaunch_overhead_s
        if sc.wake_on_request:
            policy['wake_on_request'] = True
            policy['max_parked_requests'] = sc.max_parked_requests
        config = {
            'readiness_probe': {
                'path': '/health',
                'initial_delay_seconds': sc.initial_delay_s,
                'success_threshold': 1, 'failure_threshold': 3},
            'replica_policy': policy,
            'load_balancing_policy': sc.lb_policy,
        }
        if sc.slo is not None:
            config['slo'] = sc.slo
        return config

    # ---- traffic -------------------------------------------------------
    def _synthesize(self) -> list:
        if self.sc.trace_events is not None:
            # Recorded trace (docs/simulation.md): replay the
            # arrivals verbatim — the trace IS the workload, the seed
            # only drives service-side stochastics.
            return list(self.sc.trace_events)
        from tests.load_tests import loadgen
        return loadgen.synthesize(
            self.seed, self.sc.tenants,
            duration_s=max(0.0,
                           self.sc.duration_s - self.sc.traffic_start_s))

    def _fire_request(self, ev) -> None:
        self._start_leg(_ClientCall(ev, self.kernel.now))

    def _start_leg(self, call: _ClientCall) -> None:
        """Issue (or re-issue) one logical request against the current
        LB. With the LB dead — mid crash-restart — the leg parks and
        the restarted LB replays it, exactly like an SDK retry loop
        waiting out a connection refused."""
        if self._lb is None:
            self._pending_legs.append(call)
            return
        ev = call.ev
        payload: Dict[str, Any] = {
            'tokens': ev.tokens, 'max_new_tokens': ev.max_new_tokens,
            'stream': True, 'tenant': ev.tenant}
        if call.resume:
            # The client-side half of PR 5's resume splice: tokens the
            # dead LB already delivered seed resume_from, so the new
            # stream emits only the undelivered tail.
            payload['resume_from'] = list(call.resume)
        call.req = transport_lib.SimRequest(
            '/generate', json.dumps(payload).encode(),
            headers={common.TENANT_HEADER: ev.tenant})
        call.fut = self.kernel.spawn(self._lb.handle(call.req))
        self._inflight_calls[id(call)] = call
        call.fut.add_done_callback(
            lambda f, c=call: self._on_leg_done(c, f))

    def _on_leg_done(self, call: _ClientCall,
                     fut: kernel_lib.SimFuture) -> None:
        if self._inflight_calls.pop(id(call), None) is None:
            return   # severed by an LB kill; the retry leg owns it
        ev = call.ev
        rec: Dict[str, Any] = {
            'tenant': ev.tenant, 'shed': False, 'completed': False,
            'resumed': 0, 'tokens': 0, 'ttft': None,
            'queue_wait': None, 'steps_waited': None,
            'finish_reason': None, 'itls': [],
            'lb_retries': call.retries}
        try:
            resp = fut.result()
        except BaseException as e:  # noqa: BLE001 — a gate failure, kept loud
            rec['finish_reason'] = f'exception_{type(e).__name__}: {e}'
            self.report.records.append(rec)
            self._log('request', tenant=ev.tenant,
                      outcome=rec['finish_reason'])
            return
        if isinstance(resp, transport_lib.SimStreamResponse):
            done_line = None
            token_ids: List[int] = list(call.resume)
            for line in resp.lines():
                toks = line.get('tokens')
                if isinstance(toks, list):
                    token_ids.extend(toks)
                if line.get('done'):
                    done_line = line
                if 'error' in line:
                    rec['finish_reason'] = 'stream_error'
            rec['tokens'] = len(token_ids)
            if done_line is not None and rec['finish_reason'] is None:
                rec['completed'] = True
                # Bit-identity audit: whatever failovers, resumes, and
                # LB crash-retries happened on the way, the tokens the
                # client holds must equal the deterministic unkilled
                # continuation, full length — no loss, no dupes.
                rec['tokens_ok'] = (
                    token_ids == replica_lib.expected_continuation(
                        ev.tokens, ev.max_new_tokens))
                rec['finish_reason'] = done_line.get('finish_reason')
                rec['resumed'] = int(done_line.get('resumed') or 0)
                rec['queue_wait'] = done_line.get('queue_wait_s')
                rec['steps_waited'] = done_line.get('steps_waited')
            elif rec['finish_reason'] is None:
                rec['finish_reason'] = 'truncated'
        else:
            status = getattr(resp, 'status', None)
            if status in (429, 503):
                rec['shed'] = True
                rec['finish_reason'] = f'shed_{status}'
            else:
                rec['finish_reason'] = f'http_{status}'
        self.report.records.append(rec)
        extra = {'retries': call.retries} if call.retries else {}
        self._log('request', tenant=ev.tenant,
                  outcome=rec['finish_reason'],
                  tokens=rec['tokens'], resumed=rec['resumed'],
                  **extra)

    # ---- process kills (docs/robustness.md "Crash safety") -------------
    def _crash_gate(self, window: str) -> None:
        """Installed as the VirtualCloud's crash gate: when a
        controller kill just landed, tear the cloud-facing op on the
        stack at its real crash window (after the provider
        side-effect, before the manager's DB write)."""
        if self._crash_armed:
            self._crash_armed = False
            raise cloud_lib.SimCrashError(window)

    def _kill(self, target: str, restart_delay_s: float) -> None:
        if target == 'controller':
            self._kill_controller(restart_delay_s)
        elif target == 'lb':
            self._kill_lb(restart_delay_s)
        else:
            raise ValueError(f'unknown kill target {target!r}')

    def _kill_controller(self, restart_delay_s: float) -> None:
        if self._controller is None:
            return   # already dead (overlapping kills)
        self._controller = None
        # The thread pool dies with the process: queued launches and
        # teardowns never run; the one on the stack (if any) is torn
        # by the crash gate at its window.
        self._executor.kill()
        self._crash_armed = True
        self._log('crash', target='controller')
        self.kernel.call_later(restart_delay_s,
                               self._restart_controller)

    def _restart_controller(self) -> None:
        self._crash_armed = False
        self._executor = cloud_lib.SimExecutor(self.kernel)
        self._controller = controller_lib.ServeController(
            self.SERVICE, cloud=self._cloud, executor=self._executor,
            cost_catalog=getattr(self, '_cost_catalog', None))
        self._controller.place_hook = self._on_place
        # Startup reconciliation, run TWICE: the second pass must be a
        # no-op (the idempotence half of the acceptance gate — rolled
        # into every killed replay, not just the unit test).
        rep = self._controller.rm.reconcile(now=self.kernel.now)
        rep2 = self._controller.rm.reconcile(now=self.kernel.now)
        self._log('recover', target='controller',
                  adopted=len(rep['adopted']),
                  rolled_back=len(rep['rolled_back']),
                  resolved=len(rep['resolved']),
                  resumed_teardowns=len(rep['resumed_teardowns']),
                  second_pass_noop=not any(rep2.values()))

    def _kill_lb(self, restart_delay_s: float) -> None:
        if self._lb is None:
            return
        self._lb = None
        calls = list(self._inflight_calls.values())
        self._inflight_calls.clear()
        for call in calls:
            # The process died: its proxy coroutines stop mid-await
            # (finally blocks run, like sockets closing), and the
            # client keeps what was already flushed to it.
            call.fut.cancel()
            splice = call.req.splice if call.req is not None else None
            if splice is not None:
                call.resume.extend(int(t) for t in splice.delivered)
            call.retries += 1
            self._pending_legs.append(call)
        self._log('crash', target='lb', severed=len(calls))
        self.kernel.call_later(restart_delay_s, self._restart_lb)

    def _make_lb(self) -> transport_lib.TwinLoadBalancer:
        """Build the twin's LB (initial boot and crash-restarts take
        the identical path). When the scenario arms golden probes, the
        fixture is minted from the live sim oracle — the same mint
        ``make golden-refresh`` performs — so the LB's arm-time
        fingerprint gate runs for real."""
        sc = self.sc
        fixture = fingerprint = None
        if sc.probe_interval_s is not None:
            prompt = (2, 3, 5, 7)
            golden = replica_lib.expected_continuation(list(prompt), 4)
            fingerprint = replica_lib.oracle_fingerprint()
            fixture = integrity.GoldenFixture(
                model='sim', fingerprint=fingerprint,
                prompt_tokens=prompt, max_new_tokens=4,
                token_crc=integrity.token_crc(golden))
        lb = transport_lib.TwinLoadBalancer(
            self.SERVICE, sc.lb_policy, clock=self.kernel.clock,
            model_by_url=self._model_by_url, kernel=self.kernel,
            probe_fixture=fixture, probe_fingerprint=fingerprint,
            probe_interval_s=sc.probe_interval_s,
            fleet_routing=sc.fleet_routing)
        lb.sync_interval_s = sc.lb_sync_s
        lb.stats_flush_s = sc.stats_flush_s
        lb.slo_transition_hook = self._on_slo_transition
        lb.quarantine_hook = self._on_quarantine
        return lb

    def _on_quarantine(self, url: str, replica_id: int,
                       reason: str) -> None:
        """Every quarantine verdict lands in the decision log (the
        byte-identity surface): the sdc_storm gates assert count,
        latency, and the false-positive scenarios assert absence."""
        self._log('quarantine', url=url, replica_id=replica_id,
                  reason=reason)

    def _restart_lb(self) -> None:
        self._lb = self._make_lb()
        # The crash-restart rebuild under test: ready set, affinity
        # ring, and breaker state repopulated from serve_state before
        # the first retried leg lands.
        self.kernel.spawn(self._lb.bootstrap_from_state())
        self._breakers_open = set()
        self._log('lb_restart',
                  ready=len(self._lb.policy.ready_urls),
                  replayed=len(self._pending_legs))
        legs, self._pending_legs = self._pending_legs, []
        for call in legs:
            self._start_leg(call)

    # ---- faults --------------------------------------------------------
    def _apply_fault(self, fault: Fault) -> None:
        rng = random.Random(f'fault/{self.seed}/{fault.kind}/{fault.t}')
        cloud = self._cloud
        if fault.kind == 'reclaim_storm':
            victims = [s for s in cloud.live_slices() if s.is_spot]
            n = max(1, round(len(victims) * fault.frac))
            chosen = rng.sample(victims, min(n, len(victims)))
            self._log('storm', victims=len(chosen),
                      fleet=len(victims))
            for s in chosen:
                if rng.random() < fault.notice_frac:
                    cloud.reclaim(s.cluster_name,
                                  notice_lead_s=fault.notice_lead_s)
                else:
                    cloud.reclaim(s.cluster_name)
        elif fault.kind == 'donor_reclaim':
            # Targeted spot reclaim of the active KV donor, timed by
            # the trap to land mid-transfer (docs/serving.md
            # "Disaggregated prefill/decode") — makes the gate's
            # recompute-fallback assertion non-vacuous by
            # construction instead of by storm luck.
            self._donor_trap = True
            self._log('donor_trap_armed')
        elif fault.kind == 'zone_outage':
            cloud.zone_outage(fault.zone)
        elif fault.kind == 'brownout':
            live = cloud.live_slices()
            n = max(1, round(len(live) * fault.frac))
            chosen = rng.sample(live, min(n, len(live)))
            self._log('brownout', victims=len(chosen),
                      factor=fault.factor,
                      duration_s=fault.duration_s)
            for s in chosen:
                s.model.slow_factor = fault.factor
                self.kernel.call_later(
                    fault.duration_s,
                    lambda m=s.model: setattr(m, 'slow_factor', 1.0))
        elif fault.kind == 'wedge':
            live = cloud.live_slices()
            chosen = rng.sample(live, min(fault.count, len(live)))
            self._log('wedge', victims=[s.cluster_name for s in chosen],
                      duration_s=fault.duration_s)
            for s in chosen:
                s.model.wedged = True
                self.kernel.call_later(
                    fault.duration_s,
                    lambda m=s.model: setattr(m, 'wedged', False))
        elif fault.kind == 'sdc':
            # Silent data corruption (docs/robustness.md "Data
            # integrity"): poison healthy replicas — liveness probes
            # stay green; only the golden probes / sentinel self-
            # reports can see it. Never un-poisoned: detection and
            # replacement IS the recovery path under test.
            live = [s for s in cloud.live_slices()
                    if s.model.corrupt_flavor is None]
            chosen = rng.sample(live, min(fault.count, len(live)))
            self._log('sdc', flavor=fault.flavor,
                      victims=[s.cluster_name for s in chosen])
            for s in chosen:
                s.model.poison(fault.flavor)
        else:
            raise ValueError(f'unknown fault kind {fault.kind!r}')

    # ---- control loops -------------------------------------------------
    def _on_place(self, fields: Dict[str, Any]) -> None:
        """Every FleetPlacer plan lands in the decision log — the
        cost gate's byte-identity surface (docs/cost.md)."""
        self._log('place', **fields)

    def _on_slo_transition(self, tr: Dict[str, Any]) -> None:
        """Alert transitions from the REAL burn-rate evaluator land
        in the decision log (the byte-identity surface): the
        alert-fidelity gates assert firing/resolve times and the
        zero-false-positive scenarios assert absence."""
        self._log('slo_alert', objective=tr['objective'],
                  tier=tr['tier'], state=tr['state'],
                  burn_short=tr['burn_short'],
                  burn_long=tr['burn_long'])

    def _watch_breakers(self) -> None:
        """Log breaker state EDGES into the decision log (the
        breaker-flap gate asserts open ↦ re-closed; the REAL breaker
        decides, the twin only observes)."""
        if self._lb is None:
            return
        open_now = {u for u, s in self._lb.breaker.snapshot().items()
                    if s != retry_lib.STATE_CLOSED}
        prev = getattr(self, '_breakers_open', set())
        for url in sorted(open_now - prev):
            self._log('breaker_open', url=url)
        for url in sorted(prev - open_now):
            self._log('breaker_closed', url=url)
        self._breakers_open = open_now

    def _controller_tick(self) -> None:
        if self._controller is None:
            return   # dead between kill and restart
        before = self._controller.autoscaler.target_num_replicas
        try:
            self._controller.tick(now=self.kernel.now)
        except failpoints.FailpointError:
            # The serve.controller.crash failpoint at the tick
            # boundary, armed from the environment: becomes a virtual
            # process kill (the kill-anywhere seam composes with
            # env-driven chaos like every other failpoint mirror).
            self._kill('controller', restart_delay_s=30.0)
            return
        after = self._controller.autoscaler.target_num_replicas
        if after != before:
            self._log('scale_target', target=after)

    # ---- the replay ----------------------------------------------------
    def run(self) -> SimReport:
        home = tempfile.mkdtemp(prefix='sky-tpu-twin-')
        prev_home = os.environ.get(common.HOME_ENV_VAR)
        os.environ[common.HOME_ENV_VAR] = home
        t_wall = time.perf_counter()
        try:
            with vclock.installed(self.kernel.clock):
                self._setup()
                self.kernel.run()
                if self._lb is not None:
                    self.report.lb_metrics = self._lb.lb_metrics()
                if self._cloud is not None:
                    self.report.cost = self._cloud.billing()
                if self._kv_stats:
                    self.report.kv = dict(sorted(
                        self._kv_stats.items()))
                self.report.final_fleet = self._final_fleet()
        finally:
            if prev_home is None:
                os.environ.pop(common.HOME_ENV_VAR, None)
            else:
                os.environ[common.HOME_ENV_VAR] = prev_home
            if not self.keep_home:
                # Close the scratch DB's cached connection BEFORE the
                # rmtree — an open handle would pin the unlinked file's
                # disk space (and one fd per replay) until process exit.
                db_lib.evict_under(home)
                shutil.rmtree(home, ignore_errors=True)
        self.report.wall_s = time.perf_counter() - t_wall
        self.report.events_run = self.kernel.events_run
        return self.report

    def _final_fleet(self) -> Dict[str, Any]:
        """End-of-replay convergence view: the crash gates assert a
        killed-and-recovered run lands on the SAME fleet state as the
        unkilled baseline — same ready count, nothing stuck mid-
        transition, an empty intent journal."""
        rows = serve_state.get_replicas(self.SERVICE)
        statuses: Dict[str, int] = {}
        for r in rows:
            s = r['status'].value
            statuses[s] = statuses.get(s, 0) + 1
        transitional = (ReplicaStatus.PENDING, ReplicaStatus.PROVISIONING,
                        ReplicaStatus.STARTING, ReplicaStatus.DRAINING,
                        ReplicaStatus.SHUTTING_DOWN,
                        ReplicaStatus.QUARANTINED)
        record = serve_state.get_service(self.SERVICE)
        return {
            'service_status': (record['status'].value
                               if record is not None else None),
            'ready': statuses.get('READY', 0),
            'transitional': sum(statuses.get(s.value, 0)
                                for s in transitional),
            'open_intents': serve_state.count_open_intents(self.SERVICE),
            'statuses': statuses,
            # Provider-side truth: dead-but-uncleaned slices linger
            # here — a stranded carcass cleanup is invisible to the
            # replica table (PREEMPTED is terminal) but not to the
            # cloud.
            'cloud_slices': (len(self._cloud.slices)
                             if self._cloud is not None else None),
        }

    def _setup(self) -> None:
        sc = self.sc
        # The replay's state DB is scratch (fresh dir, deleted after):
        # skip fsync so 10k+ virtual-day commits don't buy durability
        # nobody needs. Production DBs never see this pragma.
        serve_state._db().conn.execute(  # noqa: SLF001
            'PRAGMA synchronous=OFF')
        task_yaml = yaml.safe_dump({
            'name': 'twin-svc', 'run': 'serve',
            'resources': {'use_spot': bool(sc.use_spot)}})
        ok = serve_state.add_service(
            self.SERVICE, json.dumps(self._service_config()), task_yaml,
            lb_port=0, lb_policy=sc.lb_policy)
        if not ok:
            raise RuntimeError('twin service row already exists — '
                               'scratch home is not scratch')
        market = dict(sc.market or {})
        self._cloud = cloud_lib.VirtualCloud(
            self.kernel, make_replica=self._make_replica,
            log=self._log,
            zones=sc.zones or (sorted(market) or None),
            provision_delay_s=sc.provision_delay_s, seed=self.seed,
            market=market, market_horizon_s=sc.duration_s,
            kv_link_gbps=sc.kv_link_gbps,
            kv_transfer_floor_s=sc.kv_transfer_floor_s)
        self._cloud.crash_gate = self._crash_gate
        # Cost-optimized scenarios run the REAL FleetPlacer against a
        # catalog built from the same market the cloud bills — per
        # replica-hour, accelerator-agnostic ('sim').
        self._cost_catalog = None
        if sc.cost_optimized:
            from skypilot_tpu.serve import costplane
            self._cost_catalog = costplane.FleetCatalog(entries=[
                costplane.ZoneEconomics(
                    accelerator='sim', region=region, zone=zone,
                    ondemand_price=float(econ['ondemand']),
                    spot_price=float(econ['spot']),
                    preemption_rate_per_hour=float(
                        econ.get('reclaim_per_hour') or 0.0))
                for (region, zone), econ in sorted(market.items())])
        self._executor = cloud_lib.SimExecutor(self.kernel)
        self._controller = controller_lib.ServeController(
            self.SERVICE, cloud=self._cloud, executor=self._executor,
            cost_catalog=self._cost_catalog)
        self._controller.place_hook = self._on_place
        self._lb = self._make_lb()
        # Control loops at their virtual cadences. The kernel's
        # trampoline drives the LB's REAL async bodies; every await
        # inside resolves inline (the twin's _offload) so each spawn
        # completes within its event.
        self.kernel.every(sc.controller_tick_s, self._controller_tick,
                          until=sc.duration_s)

        def check_lb_crash(fut: kernel_lib.SimFuture) -> None:
            # The serve.lb.crash failpoint fires at the top of the
            # REAL _sync_once; env-armed, it becomes a virtual LB
            # process kill here (same composition rule as the
            # lb.proxy mirrors).
            if isinstance(fut._exc,  # noqa: SLF001
                          failpoints.FailpointError):
                self._kill('lb', restart_delay_s=30.0)

        def lb_sync() -> None:
            if self._lb is None:
                return
            fut = self.kernel.spawn(self._lb._sync_once())  # noqa: SLF001
            fut.add_done_callback(check_lb_crash)
            self._watch_breakers()

        def stats_flush() -> None:
            if self._lb is not None:
                self.kernel.spawn(
                    self._lb._flush_stats_once())  # noqa: SLF001

        self.kernel.every(sc.lb_sync_s, lb_sync,
                          start=sc.lb_sync_s * 0.5,
                          until=sc.duration_s)
        self.kernel.every(sc.stats_flush_s, stats_flush,
                          start=sc.stats_flush_s * 0.7,
                          until=sc.duration_s)
        # Traffic.
        for ev in self._synthesize():
            self.kernel.call_at(sc.traffic_start_s + ev.t,
                                self._fire_request, ev)
        # Faults.
        for fault in sc.faults:
            self.kernel.call_at(fault.t, self._apply_fault, fault)
        # Scheduled process kills (crash scenarios; seq-armed kills
        # fire from _log instead).
        for i, k in enumerate(self.kills):
            if k.at_t is not None:
                def fire(idx=i, spec=k) -> None:
                    if idx not in self._kills_fired:
                        self._kills_fired.add(idx)
                        self._kill(spec.target, spec.restart_delay_s)
                self.kernel.call_at(k.at_t, fire)
