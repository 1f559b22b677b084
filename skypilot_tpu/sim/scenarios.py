"""Scenario library for the digital twin (docs/robustness.md).

A :class:`Scenario` is everything one replay needs: the fleet shape
(service spec the REAL controller consumes), the traffic (a seeded
``tests/load_tests/loadgen`` tenant spec, diurnal/flash envelopes
included), the fault schedule, and the control-loop cadences. The
factories below are the shipped catalog; a new scenario is one
function returning a ``Scenario`` — see "How to add a scenario" in
docs/robustness.md.

Cadence note: fleet-scale replays run the controller/LB loops at
coarser virtual intervals than the 1s production defaults — exactly
what a 1000-replica deployment does in practice (and what the
env-tunable ``SKY_TPU_LB_SYNC_INTERVAL_S`` exists for). Gates assert
on outcomes (zero client errors, convergence, starvation bounds),
which do not depend on the cadence being 1s.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple


@dataclasses.dataclass
class Fault:
    """One scheduled fault. ``t`` is virtual seconds from replay
    start. Kinds: ``reclaim_storm`` (``frac`` of the live spot fleet;
    ``notice_frac`` of victims get a ``notice_lead_s`` advance warning
    — the drain path — the rest die hard — the resume path),
    ``zone_outage`` (every slice in ``zone``), ``brownout``
    (``frac`` of the fleet runs ``factor``x slower for
    ``duration_s``), ``wedge`` (``count`` replicas answer probes but
    fail every request for ``duration_s`` — breaker food)."""

    t: float
    kind: str
    frac: float = 0.2
    notice_frac: float = 0.7
    notice_lead_s: float = 45.0
    zone: str = ''
    duration_s: float = 120.0
    factor: float = 8.0
    count: int = 1
    # ``sdc`` faults only: ``token_flip`` serves silently wrong
    # tokens on short prompts (golden-probe food); ``nan`` trips the
    # modeled on-device sentinel (docs/robustness.md "Data
    # integrity").
    flavor: str = 'token_flip'


@dataclasses.dataclass
class KillSpec:
    """A virtual-time process kill of one control-plane component
    (docs/robustness.md "Crash safety"). ``target`` is ``'controller'``
    or ``'lb'``; the kill lands either at virtual time ``at_t`` or the
    instant decision-log entry ``at_seq`` is appended (the
    kill-anywhere sweep's boundary injection — a kill armed at a
    cloud-facing decision tears the operation at its real crash
    window via the VirtualCloud crash gate). The component restarts
    ``restart_delay_s`` later: a fresh ``ServeController`` whose
    startup reconciliation replays the journal (run twice — the gate
    asserts the second pass is a no-op), or a fresh LB rebuilt from
    the state DB, with severed client streams retried against it
    carrying ``resume_from`` (the PR 5 splice contract, client side)."""

    target: str                         # 'controller' | 'lb'
    at_t: Optional[float] = None
    at_seq: Optional[int] = None
    restart_delay_s: float = 30.0


@dataclasses.dataclass
class Scenario:
    name: str
    # Fleet shape (feeds the REAL ServiceSpec/ReplicaPolicy).
    replicas: int = 8
    max_replicas: Optional[int] = None
    # Floor override: None keeps the historical behavior (floor ==
    # ``replicas``); 0 + wake_on_request is the scale-to-zero shape.
    min_replicas: Optional[int] = None
    queue_length_threshold: Optional[float] = None
    upscale_delay_s: float = 60.0
    downscale_delay_s: float = 600.0
    use_spot: bool = True
    lb_policy: str = 'round_robin'
    # Cost plane (docs/cost.md): when ``cost_optimized`` the REAL
    # FleetPlacer runs inside the twin's controller against a
    # FleetCatalog built from ``market`` (per-(region, zone)
    # {'ondemand', 'spot', 'reclaim_per_hour'} — prices per
    # replica-hour, reclaims per slice-hour). The same market dict
    # drives VirtualCloud's pre-sampled Poisson reclaim streams and
    # its billing meters, market or not cost-optimized.
    cost_optimized: bool = False
    market: Optional[Dict[Tuple[str, str], Dict[str, float]]] = None
    relaunch_overhead_s: float = 180.0
    # Scale-to-zero (docs/cost.md "Scale to zero").
    wake_on_request: bool = False
    max_parked_requests: int = 32
    # Traffic (loadgen tenant spec; envelope shapes welcome).
    tenants: Dict[str, Dict[str, Any]] = dataclasses.field(
        default_factory=dict)
    traffic_start_s: float = 420.0
    duration_s: float = 3600.0
    # Modeled replica shape (REAL scheduler inside).
    scheduler: str = 'fcfs'
    tenant_weights: Optional[Dict[str, float]] = None
    max_queue_requests: Optional[int] = 64
    max_queue_tokens: Optional[int] = None
    slots: int = 8
    perf_scale: float = 1.0
    # Virtual cloud.
    provision_delay_s: Tuple[float, float] = (30.0, 90.0)
    zones: Optional[List[Tuple[str, str]]] = None
    # Control-loop cadences (virtual seconds).
    controller_tick_s: float = 15.0
    lb_sync_s: float = 5.0
    stats_flush_s: float = 10.0
    initial_delay_s: float = 300.0
    faults: List[Fault] = dataclasses.field(default_factory=list)
    # Process kills (crash scenarios embed one; the kill-anywhere
    # sweep injects its own per boundary).
    kills: List[KillSpec] = dataclasses.field(default_factory=list)
    # Service-level objectives (docs/observability.md "SLOs and
    # alerting"): flows through the REAL spec validation into the
    # service row, where the REAL LB's burn-rate evaluator loads it —
    # the alert-fidelity gates in tests/sim/test_slo_alerts.py arm
    # these. None = no objectives, the SLO layer stays inert.
    slo: Optional[List[Dict[str, Any]]] = None
    # Data-integrity plane (docs/robustness.md "Data integrity"):
    # a per-replica golden-probe cadence arms the REAL LB probe
    # scheduler against the sim oracle's golden fixture. None = probes
    # unarmed — every pre-existing scenario replays byte-identically.
    probe_interval_s: Optional[float] = None
    # Disaggregated prefill/decode (docs/serving.md "Disaggregated
    # prefill/decode"): ``kv_page`` > 0 arms the modeled KV prefix
    # tier — replicas index chained page hashes, the REAL
    # FleetPrefixIndex folds them at the LB, donor pulls ride the
    # VirtualCloud's transfer-latency curve. 0 keeps every
    # pre-existing scenario byte-identical. ``prefill_fraction``
    # carves that share of launches into dedicated prefill replicas
    # (role-steered by the LB, donors for the decode pool);
    # ``fleet_routing`` False is the owner-only baseline the hit-rate
    # gate compares against.
    kv_page: int = 0
    kv_bytes_per_token: int = 65536
    kv_link_gbps: float = 10.0
    kv_transfer_floor_s: float = 0.005
    # Idle TTL on a replica's indexed prefixes — the model of
    # decode-page-pressure eviction (a prefix nobody re-touches loses
    # its pages to the allocator). 0 = never expires.
    kv_ttl_s: float = 0.0
    prefill_fraction: float = 0.0
    fleet_routing: bool = True
    # Prefill budget override (tokens per virtual step); None keeps
    # the PerfModel default. Disagg scenarios lower it so warm-prefix
    # prefill is measurably cheaper than cold.
    prefill_tokens_per_step: Optional[float] = None
    # Recorded-trace override (docs/simulation.md): a list of
    # tracefmt.TraceEvent arrivals replayed VERBATIM (offsets from
    # traffic_start_s) instead of synthesizing from ``tenants`` —
    # how `sky-tpu simulate --trace` and literal-trace replays drive
    # the twin. None keeps the loadgen path.
    trace_events: Optional[List[Any]] = None


def reclaim_storm(*, replicas: int = 40, duration_s: float = 2400.0,
                  storm_frac: float = 0.25,
                  rps: float = 10.0) -> Scenario:
    """A quarter-fleet spot-reclaim storm mid-replay: half the victims
    get the advance notice (drain handoff), the rest die hard
    mid-stream (resume splice). Streams run long enough (32 tokens at
    a 2x-scaled ITL curve) that hard kills reliably land MID-stream —
    the resume gate must be non-vacuous. Gate: ZERO client-visible
    errors."""
    storm_t = duration_s * 0.5
    return Scenario(
        name='reclaim_storm', replicas=replicas, use_spot=True,
        duration_s=duration_s, perf_scale=2.0,
        tenants={'prod': {'rps': rps, 'prompt_mean': 48,
                          'prompt_max': 256, 'max_new': 32,
                          'until': duration_s * 0.75}},
        faults=[Fault(t=storm_t, kind='reclaim_storm',
                      frac=storm_frac, notice_frac=0.5)])


def incident_page_storm(*, replicas: int = 4,
                        duration_s: float = 1500.0,
                        rps: float = 16.0) -> Scenario:
    """The incident-replay seed scenario (docs/simulation.md): a
    3-of-4 reclaim storm under enough load that the surviving replica
    saturates and the ttft_p99 PAGE fires — which writes an
    ``slo_page`` fleet dump the converter exports. Every knob the
    flight recorder does NOT capture (slots, scheduler, perf model)
    stays at the Scenario DEFAULT, so the converter's reconstruction
    replays against the same capacity model that grew the dump."""
    storm_t = duration_s * 0.45
    return Scenario(
        name='incident_page_storm', replicas=replicas, use_spot=True,
        duration_s=duration_s,
        # Replacements stay out long enough for the 5m page window to
        # breach (the multi-window rule needs a sustained burn).
        provision_delay_s=(420.0, 480.0),
        tenants={'prod': {'rps': rps, 'prompt_mean': 48,
                          'prompt_max': 256, 'max_new': 32,
                          'shared_prefix_frac': 0.3,
                          'until': duration_s * 0.85}},
        slo=[{'metric': 'ttft_p99', 'threshold_s': 2.0,
              'target': 0.99},
             {'metric': 'itl_p99', 'threshold_s': 0.5,
              'target': 0.99},
             {'metric': 'availability', 'target': 0.999},
             {'metric': 'shed_rate', 'target': 0.99}],
        faults=[Fault(t=storm_t, kind='reclaim_storm', frac=0.75,
                      notice_frac=0.5)])


def flash_crowd(*, base_replicas: int = 2, max_replicas: int = 10,
                duration_s: float = 5400.0) -> Scenario:
    """A 15x flash crowd against the REAL QueueLengthAutoscaler: the
    crowd saturates the base fleet (slots x step-time make per-replica
    throughput ~2 rps), queue depth crosses the threshold, the target
    climbs with hysteresis, and drains back down after the crowd.
    Gate: scale-up happened, settled back, and the target moved in at
    most two directions (up, then down — no oscillation)."""
    flash_at = duration_s * 0.3
    return Scenario(
        name='flash_crowd', replicas=base_replicas,
        max_replicas=max_replicas, queue_length_threshold=6.0,
        upscale_delay_s=30.0, downscale_delay_s=240.0,
        duration_s=duration_s, slots=2, max_queue_requests=64,
        perf_scale=3.0, controller_tick_s=15.0,
        provision_delay_s=(20.0, 45.0),
        tenants={'web': {
            'rps': 1.0, 'prompt_mean': 24, 'prompt_max': 64,
            'max_new': 12, 'until': duration_s * 0.8,
            'envelope': {'kind': 'flash', 'at': flash_at,
                         'duration_s': 420.0, 'mult': 15.0}}})


def regional_failover(*, replicas: int = 12,
                      duration_s: float = 2400.0) -> Scenario:
    """A whole zone dies at once. Gates: the fleet relaunches to
    target, every relaunch lands OUTSIDE the dead zone (spot placer's
    blocked placements), clients ride through on retry/resume."""
    return Scenario(
        name='regional_failover', replicas=replicas,
        duration_s=duration_s,
        tenants={'prod': {'rps': 4.0, 'prompt_mean': 32,
                          'prompt_max': 96, 'max_new': 10,
                          'until': duration_s * 0.75}},
        faults=[Fault(t=duration_s * 0.5, kind='zone_outage',
                      zone='sim-r1-a')])


def slow_brownout(*, replicas: int = 8,
                  duration_s: float = 2400.0) -> Scenario:
    """A quarter of the fleet browns out (8x slower steps, probes
    still green). Gate: no client-visible errors — slow is not dead,
    and the breaker must NOT amputate replicas that still answer."""
    return Scenario(
        name='slow_brownout', replicas=replicas, duration_s=duration_s,
        lb_policy='least_load',
        tenants={'prod': {'rps': 5.0, 'prompt_mean': 24,
                          'prompt_max': 64, 'max_new': 8,
                          'until': duration_s * 0.75}},
        faults=[Fault(t=duration_s * 0.45, kind='brownout', frac=0.25,
                      duration_s=600.0, factor=8.0)])


def breaker_flap(*, replicas: int = 6,
                 duration_s: float = 2400.0) -> Scenario:
    """One replica wedges (probes green, every request fails) for two
    breaker cooldowns, then heals. Gates: the breaker OPENS (stops the
    bleeding), re-CLOSES after recovery, and no client ever sees the
    wedge (pre-stream failover)."""
    return Scenario(
        name='breaker_flap', replicas=replicas, duration_s=duration_s,
        tenants={'prod': {'rps': 6.0, 'prompt_mean': 16,
                          'prompt_max': 48, 'max_new': 8,
                          'until': duration_s * 0.75}},
        faults=[Fault(t=duration_s * 0.45, kind='wedge', count=1,
                      duration_s=300.0)])


def sdc_storm(*, replicas: int = 8,
              duration_s: float = 2400.0) -> Scenario:
    """Silent data corruption mid-fleet (docs/robustness.md "Data
    integrity"): one replica starts flipping tokens (silently wrong
    bytes, liveness probes green) and later another's logits go
    non-finite (the modeled on-device sentinel). Golden probes run
    every ``probe_interval_s`` against every READY replica. Gates:
    every poisoned replica QUARANTINED within three probe rounds and
    replaced by the autoscaler; every COMPLETED client stream
    bit-identical to a same-seed uncorrupted run (the quarantine cut
    + resume splice — non-vacuous: streams are long enough to be in
    flight at quarantine time); zero false quarantines.

    Tenant prompts are sized ≥ ``prompt_mean/2`` = 12 tokens — above
    the modeled corruptor's short-prompt reach (the 4-token golden
    probe is inside it), mirroring real SDC's address-dependence:
    the probe sees corruption tenants have not hit yet."""
    return Scenario(
        name='sdc_storm', replicas=replicas, duration_s=duration_s,
        perf_scale=2.0, probe_interval_s=20.0,
        tenants={'prod': {'rps': 4.0, 'prompt_mean': 24,
                          'prompt_max': 64, 'max_new': 32,
                          'until': duration_s * 0.75}},
        faults=[Fault(t=duration_s * 0.40, kind='sdc', count=1,
                      flavor='token_flip'),
                Fault(t=duration_s * 0.55, kind='sdc', count=1,
                      flavor='nan')])


def wfq_fleet(*, replicas: int = 4, duration_s: float = 900.0,
              aggressor: bool = True) -> Scenario:
    """Fleet-scale starvation gate: the REAL wfq scheduler (weights +
    per-tenant quotas) inside every modeled replica, a 10:1 aggressor
    flood through the REAL LB. Run once with the aggressor and once
    without (same seed) — the victim's scheduler-virtual steps_waited
    must hold the 3x bound with zero victim sheds."""
    tenants: Dict[str, Dict[str, Any]] = {
        'victim': {'rps': 2.0, 'burst': 3, 'prompt_mean': 12,
                   'prompt_max': 24, 'max_new': 8,
                   'until': duration_s * 0.7}}
    if aggressor:
        tenants['aggressor'] = {
            'rps': 20.0, 'burst': 10, 'prompt_mean': 24,
            'prompt_max': 48, 'max_new': 8,
            'until': duration_s * 0.7}
    # Saturation is the point: per-replica throughput ~= slots /
    # (max_new x step) ~= 2 rps, fleet ~= 8 rps, offered load ~= 22 —
    # the aggressor MUST outrun its share or the quota gate is
    # vacuous.
    return Scenario(
        name='wfq_fleet', replicas=replicas, duration_s=duration_s,
        scheduler='wfq', slots=4, max_queue_requests=16,
        perf_scale=5.0,
        tenant_weights={'victim': 2.0, 'aggressor': 1.0},
        tenants=tenants)


def crash_controller_mid_storm(*, replicas: int = 12,
                               duration_s: float = 1800.0) -> Scenario:
    """kill -9 the controller in the MIDDLE of a reclaim storm — half
    the fleet's recovery (drains in flight, replacements mid-launch,
    carcass cleanups queued) dies with it. Gates: the restarted
    controller's startup reconciliation converges the fleet back to
    target (adopting orphans it launched but never recorded, finishing
    half-done teardowns), reconciliation is idempotent, and clients
    ride through on the LB's retry/resume with ZERO visible errors."""
    storm_t = duration_s * 0.4
    return Scenario(
        name='crash_controller_mid_storm', replicas=replicas,
        use_spot=True, duration_s=duration_s, perf_scale=2.0,
        tenants={'prod': {'rps': 3.0, 'prompt_mean': 32,
                          'prompt_max': 128, 'max_new': 12,
                          'until': duration_s * 0.7}},
        faults=[Fault(t=storm_t, kind='reclaim_storm', frac=0.3,
                      notice_frac=0.5)],
        # Landing 20s after the storm hits puts the kill inside the
        # drain/replace churn (controller tick is 15s: the first
        # recovery tick has run, its launches/drains are in flight).
        kills=[KillSpec(target='controller', at_t=storm_t + 20.0,
                        restart_delay_s=45.0)])


def crash_lb_mid_stream(*, replicas: int = 6,
                        duration_s: float = 1200.0) -> Scenario:
    """kill -9 the LB with token streams in flight. The severed
    clients retry against the restarted LB with
    ``resume_from = delivered`` (the SDK-visible half of PR 5's resume
    splice), which rebuilds its replica set from the state DB before
    serving. Gates: zero client-visible errors, retried streams
    bit-identical to unkilled runs, retries non-vacuous."""
    kill_t = duration_s * 0.55
    # Streams must reliably be IN FLIGHT at the kill instant (the
    # resume-retry gate is vacuous otherwise): 32 tokens at a
    # 6x-scaled ITL curve keeps each stream alive ~6 virtual seconds,
    # so 3 rps holds ~19 concurrent through the kill window even at a
    # burst trough — while fleet capacity (~8 rps) stays ahead of
    # offered load, so admission never sheds and the zero-error gate
    # is pure.
    return Scenario(
        name='crash_lb_mid_stream', replicas=replicas,
        duration_s=duration_s, perf_scale=6.0,
        tenants={'prod': {'rps': 3.0, 'prompt_mean': 48,
                          'prompt_max': 128, 'max_new': 32,
                          'until': duration_s * 0.7}},
        kills=[KillSpec(target='lb', at_t=kill_t,
                        restart_delay_s=10.0)])


def crash_sweep(*, replicas: int = 4,
                duration_s: float = 600.0) -> Scenario:
    """The kill-anywhere sweep's BASE replay: a small spot fleet, a
    half-fleet storm with a notice/hard mix, steady short streams —
    small enough that one full replay is milliseconds, rich enough
    that its decision log crosses every lifecycle edge (launch, drain,
    terminate, notice, reclaim, scale). ``sim/crash.py`` replays it
    once unkilled, then once per control-plane decision boundary per
    target with a kill injected there (docs/robustness.md
    "Crash safety")."""
    # The storm MUST land inside the traffic window: its drains, hard
    # kills, and replacement launches are the boundaries where kills
    # meet in-flight streams. 24-token streams at a 4x ITL curve live
    # ~2-3 virtual seconds, so several ride through every storm-window
    # boundary — LB kills sever real streams (client resume-retry
    # non-vacuous) and the storm's hard kills land mid-stream (LB
    # resume splice non-vacuous). Sized for tier-1 wall clock: every
    # killed replay of the sweep replays this whole scenario.
    storm_t = duration_s * 0.7
    return Scenario(
        name='crash_sweep', replicas=replicas, use_spot=True,
        duration_s=duration_s, perf_scale=4.0,
        traffic_start_s=240.0,
        tenants={'prod': {'rps': 2.0, 'burst': 2, 'prompt_mean': 24,
                          'prompt_max': 64, 'max_new': 24,
                          'until': duration_s * 0.6}},
        faults=[Fault(t=storm_t, kind='reclaim_storm', frac=0.5,
                      notice_frac=0.5)])


def fleet_storm_24h(*, replicas: int = 1000,
                    requests: float = 0.12) -> Scenario:
    """THE acceptance gate: a 24h diurnal day at 1000 modeled
    replicas, a 20%-fleet reclaim storm at the evening peak — replayed
    in seconds of wall clock, byte-identical per seed. ``requests``
    scales the diurnal rate (0.12 rps peak-mean ≈ several thousand
    requests over the day — the decision density that matters; the
    fleet-size axis is what this gate exists to prove)."""
    day = 86400.0
    return Scenario(
        name='fleet_storm_24h', replicas=replicas, use_spot=True,
        duration_s=day + 2400.0, traffic_start_s=900.0,
        controller_tick_s=60.0, lb_sync_s=60.0, stats_flush_s=45.0,
        provision_delay_s=(60.0, 240.0), initial_delay_s=600.0,
        max_queue_requests=128,
        tenants={'world': {
            'rps': requests, 'prompt_mean': 48, 'prompt_max': 192,
            'max_new': 10, 'until': day,
            'envelope': {'kind': 'diurnal', 'period_s': day,
                         'low': 0.15}}},
        # Notice lead MUST clear the controller tick cadence or the
        # drain never happens: a notice only turns into a planned
        # handoff when a tick observes it before the provider's kill.
        faults=[Fault(t=900.0 + day * 0.58, kind='reclaim_storm',
                      frac=0.2, notice_lead_s=240.0)])


def spot_market_week(*, replicas: int = 6, days: float = 7.0,
                     cost_optimized: bool = True,
                     use_spot: bool = True) -> Scenario:
    """THE cost-plane acceptance gate (docs/cost.md): a week of
    diurnal traffic over a three-zone spot market with distinct
    prices and reclaim intensities. Run cost-optimized (the REAL
    FleetPlacer chooses the spot/on-demand mix per tick) and once
    more all-on-demand (``cost_optimized=False, use_spot=False``,
    same seed) — the gate asserts real dollars saved at SLO: billed
    total well under the baseline, ZERO client-visible errors, ZERO
    page-tier SLO alert transitions, and the placement decision log
    byte-identical across same-seed replays.

    Deliberately a FIXED-target fleet (no ``queue_length_threshold``):
    the week-scale cadences (90s stats flush) sit far beyond the
    inflight gauge's 30s staleness window, so a queue-length
    autoscaler would always read zero here — the market mix, not the
    replica count, is what this scenario exercises."""
    day = 86400.0
    duration = days * day + 3600.0
    market = {
        ('sim-r1', 'sim-r1-a'): {'ondemand': 10.0, 'spot': 3.0,
                                 'reclaim_per_hour': 0.05},
        ('sim-r1', 'sim-r1-b'): {'ondemand': 10.0, 'spot': 3.5,
                                 'reclaim_per_hour': 0.12},
        ('sim-r2', 'sim-r2-a'): {'ondemand': 11.0, 'spot': 4.2,
                                 'reclaim_per_hour': 0.02},
    }
    return Scenario(
        name='spot_market_week', replicas=replicas,
        use_spot=use_spot, cost_optimized=cost_optimized,
        market=market, relaunch_overhead_s=420.0,
        zones=sorted(market),
        duration_s=duration, traffic_start_s=1800.0,
        controller_tick_s=120.0, lb_sync_s=120.0, stats_flush_s=90.0,
        provision_delay_s=(120.0, 300.0), initial_delay_s=600.0,
        tenants={'world': {
            'rps': 0.03, 'prompt_mean': 32, 'prompt_max': 96,
            'max_new': 8, 'until': days * day,
            'envelope': {'kind': 'diurnal', 'period_s': day,
                         'low': 0.25}}},
        # Armed objectives make the zero-page gate non-vacuous: a
        # placer that chases cheap spot into reclaim churn pages here.
        slo=[{'metric': 'ttft_p99', 'threshold_s': 2.0,
              'target': 0.99},
             {'metric': 'availability', 'target': 0.999}])


def scale_to_zero(*, duration_s: float = 7200.0) -> Scenario:
    """Scale-to-zero lifecycle (docs/cost.md "Scale to zero"): the
    fleet parks (min_replicas 0) before traffic arrives, the first
    request parks in the LB's bounded wake queue, the inflight gauge
    wakes the autoscaler, a replica cold-starts, the parked requests
    drain, and after the burst the fleet parks again. Gates: at least
    one real cold start sampled (park -> ready wall time), zero
    client-visible errors, final service status PARKED.

    ``stats_flush_s`` MUST stay under the inflight gauge's 30s
    staleness window — a coarser cadence reads parked requests as
    zero and the fleet never wakes."""
    return Scenario(
        name='scale_to_zero', replicas=1, max_replicas=3,
        min_replicas=0, wake_on_request=True, max_parked_requests=32,
        queue_length_threshold=4.0,
        upscale_delay_s=15.0, downscale_delay_s=600.0,
        duration_s=duration_s, traffic_start_s=2400.0,
        controller_tick_s=15.0, lb_sync_s=10.0, stats_flush_s=20.0,
        provision_delay_s=(30.0, 90.0), initial_delay_s=120.0,
        # Trace times are RELATIVE to traffic_start_s: a 900s burst at
        # t=2400..3300, then quiet — the fleet must be PARKED at both
        # ends of the replay.
        tenants={'jobs': {'rps': 0.2, 'prompt_mean': 24,
                          'prompt_max': 64, 'max_new': 8,
                          'until': 900.0}})


def disagg_fleet(*, replicas: int = 1000, duration_s: float = 3600.0,
                 fleet_routing: bool = True,
                 rps: float = 2.0) -> Scenario:
    """THE disaggregation acceptance gate (docs/serving.md
    "Disaggregated prefill/decode"): a 1000-replica fleet serving a
    shared-system-prompt diurnal cohort through the REAL cache-aware
    LB with the fleet prefix index armed, a 20% spot-reclaim storm
    landing mid-window so donors die with transfers pending. Run
    once fleet-routed and once ``fleet_routing=False`` (owner-only
    consistent hashing, same seed): the gates assert the fleet index
    at least DOUBLES the warm-prefix rate, TTFT p99 improves, zero
    client-visible errors ride through the storm (donor-death
    recompute fallback non-vacuous), and two same-seed replays emit
    byte-identical decision logs.

    Prompt shape: a 48-token shared system prompt (3 pages at
    ``kv_page`` 16) on ~nine of ten requests, heavy-tail user tails.
    48 < the LB's 64-token affinity lead, so the owner-only baseline
    keys on prefix+tail and SCATTERS the cohort across the ring —
    each replica sees a cohort request every ~8 virtual minutes,
    past the 300 s idle TTL (the decode-page-pressure eviction
    model), so its prefix is cold again.  The fleet index instead
    keys on the longest indexed chain link and steers to live
    holders, which stay hot.  ``prefill_tokens_per_step`` 32 makes a
    cold ~72-token prefill cost ~3 virtual steps and a warm one 1 —
    the TTFT gap the transfer either buys (fleet) or does not."""
    storm_t = duration_s * 0.55
    return Scenario(
        name='disagg_fleet', replicas=replicas, use_spot=True,
        duration_s=duration_s, traffic_start_s=600.0,
        controller_tick_s=60.0, lb_sync_s=30.0, stats_flush_s=45.0,
        provision_delay_s=(60.0, 240.0), initial_delay_s=480.0,
        lb_policy='cache_aware', max_queue_requests=64,
        perf_scale=2.0, prefill_tokens_per_step=32.0,
        kv_page=16, kv_ttl_s=300.0, prefill_fraction=0.1,
        fleet_routing=fleet_routing,
        tenants={'world': {
            'rps': rps, 'prompt_mean': 48, 'prompt_max': 128,
            'max_new': 10, 'shared_prefix_frac': 0.9,
            'prefix_tokens': 48, 'until': duration_s * 0.8,
            'envelope': {'kind': 'diurnal', 'period_s': duration_s,
                         'low': 0.3}}},
        faults=[
            # Targeted reclaim of the active donor, trapped to land
            # mid-transfer — the recompute-fallback gate's worst case,
            # deterministic across seeds (a storm alone only fells
            # the donor by luck).
            Fault(t=duration_s * 0.4, kind='donor_reclaim'),
            Fault(t=storm_t, kind='reclaim_storm', frac=0.2,
                  notice_frac=0.25, notice_lead_s=120.0)])


SCENARIOS = {
    'reclaim_storm': reclaim_storm,
    'incident_page_storm': incident_page_storm,
    'flash_crowd': flash_crowd,
    'regional_failover': regional_failover,
    'slow_brownout': slow_brownout,
    'breaker_flap': breaker_flap,
    'sdc_storm': sdc_storm,
    'wfq_fleet': wfq_fleet,
    'crash_controller_mid_storm': crash_controller_mid_storm,
    'crash_lb_mid_stream': crash_lb_mid_stream,
    'crash_sweep': crash_sweep,
    'fleet_storm_24h': fleet_storm_24h,
    'spot_market_week': spot_market_week,
    'scale_to_zero': scale_to_zero,
    'disagg_fleet': disagg_fleet,
}
