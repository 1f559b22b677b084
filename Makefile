# Developer entry points. `make lint` is the pre-commit gate: the same
# AST invariant checkers CI runs (docs/static-analysis.md), scoped to
# your git-changed files for speed; `make lint-full` is the whole
# package (what the tier-1 test and the deploy/Dockerfile `lint` stage
# enforce).

PYTHON ?= python

.PHONY: lint lint-full lint-json test-analysis profile-smoke sim-smoke sim-crash-sweep slo-smoke cost-smoke integrity-smoke disagg-smoke golden-refresh incident-smoke simulate-smoke

lint:
	$(PYTHON) -m skypilot_tpu.client.cli lint --changed

lint-full:
	$(PYTHON) -m skypilot_tpu.client.cli lint

lint-json:
	$(PYTHON) -m skypilot_tpu.client.cli lint --json

test-analysis:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/unit_tests/test_analysis.py -q

# Flight-recorder smoke (docs/observability.md "Flight recorder"): a
# tiny in-process workload with the recorder on, a forced anomaly
# dump, and Perfetto-schema validation of both the live export and
# the span-store round trip. Exit 0 = the black box works end to end.
profile-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) -m skypilot_tpu.observability.stepline

# Digital-twin smoke (docs/robustness.md "Digital twin"): replay the
# reclaim-storm scenario against the REAL control plane in virtual
# time, twice, and fail on any client-visible error or a decision-log
# byte mismatch between the two same-seed runs.
sim-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) -m skypilot_tpu.sim --scenario reclaim_storm --verify-determinism

# SLO alert round-trip smoke (docs/observability.md "SLOs and
# alerting"): replay the reclaim-storm scenario in the digital twin
# with a TTFT objective armed and assert the whole alert loop end to
# end — the page tier fires after the storm, clears after recovery,
# the firing edge wrote a flight-recorder fleet dump, and the
# availability objective stayed silent (zero false positives on a
# zero-error storm).
slo-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) -m skypilot_tpu.observability.slo

# Kill-anywhere crash-consistency sweep (docs/robustness.md "Crash
# safety"): replay the crash_sweep storm once unkilled, then once per
# control-plane decision boundary with a virtual kill -9 of the
# controller (and separately the LB) injected there; run the whole
# sweep twice and fail on any client-visible error, convergence
# mismatch, non-idempotent recovery, or decision-log byte mismatch.
sim-crash-sweep:
	JAX_PLATFORMS=cpu $(PYTHON) -m skypilot_tpu.sim --crash-sweep --verify-determinism

# Cost-plane smoke (docs/cost.md): replay the seeded spot-market
# scenario in the digital twin cost-optimized and all-on-demand (same
# seed), print the dollars saved and the SLO page-alert count, and
# fail on any page alert, any client-visible error, or zero savings.
cost-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) -m skypilot_tpu.serve.costplane

# Data-integrity smoke (docs/robustness.md "Data integrity"): replay
# the sdc_storm scenario in the digital twin — token-flip and NaN
# corruption mid-traffic — and assert detect → quarantine → replace
# with zero wrong tokens in completed streams; then replay the
# brownout scenario with probes armed and assert zero false
# quarantines (slow is not corrupt).
integrity-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) -m skypilot_tpu.observability.integrity

# Disaggregation smoke (docs/serving.md "Disaggregated
# prefill/decode"): replay the 1000-replica shared-system-prompt
# diurnal storm in the digital twin — prefill donors, decode pullers,
# a donor reclaimed mid-transfer — twice with the same seed, and fail
# on a fleet prefix hit rate below 2x owner-only routing, any
# client-visible error, a vacuous donor-death fallback, or a
# decision-log byte mismatch between the two runs.
disagg-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) -m skypilot_tpu.sim --scenario disagg_fleet --verify-determinism

# Incident-replay smoke (docs/simulation.md "Incident replay"): run
# the cold-start-crush + reclaim-storm scenario in the digital twin
# with the flight recorder armed, export the triggering slo_page
# fleet dump to a versioned incident trace, replay it, and fail
# unless the replay reproduces the recorded page-alert classes in
# the recorded order, two same-seed exports are byte-identical, and
# two same-seed replays produce byte-identical decision logs.
incident-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) -m skypilot_tpu.observability.incident

# What-if simulation smoke (docs/simulation.md "What-if API"):
# synthesize a loadgen trace, round-trip it through the versioned
# trace format, run `sky-tpu simulate` headless twice with the same
# seed (must match byte for byte), then a one-knob sweep with ranked
# results and per-run decision-log digests.
simulate-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) -m skypilot_tpu.sim.whatif

# Re-mint the golden-probe fixture store
# (skypilot_tpu/observability/golden_probes.json) after a model,
# tokenizer, or sim-oracle change. A stale golden refuses to ARM
# (StaleGoldenError) instead of quarantining the whole fleet.
golden-refresh:
	JAX_PLATFORMS=cpu $(PYTHON) -m skypilot_tpu.observability.integrity --refresh
