"""Fleet-scale digital twin: deterministic virtual-time chaos for the
REAL control plane (docs/robustness.md "Digital twin").

FoundationDB-style deterministic simulation instead of wall-clock
chaos: a discrete-event kernel owns a seeded virtual clock
(``utils/vclock``) and an in-process virtual transport, and drives the
REAL ``LoadBalancer`` (policies, breakers, resume splicing, shed
routing), the REAL ``ServeController`` tick + autoscalers, the REAL
``ReplicaManager`` lifecycle state machine, and the REAL
``infer/sched`` admission code (fcfs/EDF/wfq quotas) — against modeled
replicas whose step time follows an invented curve
(``replica.PerfModel.default``). A 24h diurnal trace at 1000 modeled
replicas, with spot-reclaim storms and tenant bursts, replays in
seconds of tier-1 wall clock;
the same seed produces a byte-identical decision log.

Layout:

- ``kernel``: the event heap, virtual clock, and the coroutine
  trampoline that drives the LB's real ``async def handle`` without an
  asyncio loop.
- ``replica``: modeled replicas — a REAL scheduler instance fronting
  virtual decode slots whose step time follows ``PerfModel``'s curve.
- ``cloud``: the ``CloudAdapter`` implementation (virtual provisioner,
  probes, preemption notices, drains) + the deterministic executor the
  replica manager's thread pool is swapped for.
- ``transport``: the LB subclass whose only overrides are the
  transport seams (proxy attempts, metrics fetch, DB offload).
- ``twin``: the orchestrator — wires state DB, controller, LB, trace
  and fault schedule into one run; emits the decision log + report.
- ``scenarios``: the scenario library (flash crowd, reclaim storm,
  regional failover, brownout, breaker flap) and its gates.
"""
from skypilot_tpu.sim.crash import run_crash_sweep
from skypilot_tpu.sim.scenarios import (SCENARIOS, KillSpec, Scenario,
                                        breaker_flap,
                                        crash_controller_mid_storm,
                                        crash_lb_mid_stream,
                                        crash_sweep, disagg_fleet,
                                        flash_crowd,
                                        fleet_storm_24h,
                                        incident_page_storm,
                                        reclaim_storm,
                                        regional_failover, sdc_storm,
                                        slow_brownout, wfq_fleet)
from skypilot_tpu.sim.twin import DigitalTwin, SimReport

__all__ = ['DigitalTwin', 'KillSpec', 'SCENARIOS', 'Scenario',
           'SimReport', 'breaker_flap', 'crash_controller_mid_storm',
           'crash_lb_mid_stream', 'crash_sweep', 'disagg_fleet',
           'flash_crowd', 'fleet_storm_24h', 'incident_page_storm',
           'reclaim_storm',
           'regional_failover', 'run_crash_sweep', 'sdc_storm',
           'slow_brownout', 'wfq_fleet']
