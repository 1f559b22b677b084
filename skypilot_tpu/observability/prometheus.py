"""Prometheus text exposition for the serving tier.

The LB's ``/-/metrics`` and each replica's ``/metrics`` are JSON by
design (they feed `serve status` and the benchmark directly); this
module is the exposition wrapper both grow behind
``?format=prometheus`` so a scrape-based stack ingests the same
numbers without a JSON exporter sidecar.

Exposed families are an **explicit, curated literal map** — never a
mechanical flatten — for two reasons: exposition names are a public
API (dashboards break when they drift), and `sky-tpu lint`
(SKY-REGISTRY) cross-checks every ``sky_tpu_*`` family named here
against docs/observability.md's "Prometheus exposition" catalog, both
directions. Add a family => add a catalog row.

Label values are client-controlled (tenant ids ride
``X-SkyTpu-Tenant``): every label is passed through the span store's
:func:`~skypilot_tpu.observability.store.sanitize_label` rule so a
hostile id cannot corrupt the exposition format (quotes, newlines,
unbounded length).
"""
from __future__ import annotations

from typing import Any, Dict, List, Mapping, Optional, Tuple

from skypilot_tpu.observability import store as store_lib


def lb_exposition() -> Dict[str, Tuple[str, str]]:
    """Scalar LB ``lb_metrics()`` keys -> (family, type). Counters
    are monotonic LB edge counters; gauges are point-in-time."""
    return {
        'requests_total': ('sky_tpu_lb_requests_total', 'counter'),
        'requests_failed': ('sky_tpu_lb_requests_failed', 'counter'),
        'requests_no_replica': (
            'sky_tpu_lb_requests_no_replica', 'counter'),
        'requests_retried': (
            'sky_tpu_lb_requests_retried', 'counter'),
        'requests_resumed': (
            'sky_tpu_lb_requests_resumed', 'counter'),
        'requests_shed': ('sky_tpu_lb_requests_shed', 'counter'),
        'ready_replicas': ('sky_tpu_lb_ready_replicas', 'gauge'),
        'engine_queue_depth': (
            'sky_tpu_lb_engine_queue_depth', 'gauge'),
        'ttft_p50_s': ('sky_tpu_lb_ttft_p50_seconds', 'gauge'),
        'ttft_p90_s': ('sky_tpu_lb_ttft_p90_seconds', 'gauge'),
        'ttft_p99_s': ('sky_tpu_lb_ttft_p99_seconds', 'gauge'),
        'itl_p50_s': ('sky_tpu_lb_itl_p50_seconds', 'gauge'),
        'itl_p99_s': ('sky_tpu_lb_itl_p99_seconds', 'gauge'),
        'engine_tokens_per_step': (
            'sky_tpu_lb_engine_tokens_per_step', 'gauge'),
        'engine_tokens_per_sec_w': (
            'sky_tpu_lb_engine_tokens_per_sec', 'gauge'),
        'prefix_hit_rate_w': (
            'sky_tpu_lb_prefix_hit_rate', 'gauge'),
        'history_window_s': (
            'sky_tpu_lb_history_window_seconds', 'gauge'),
        'slo_alerts_firing': (
            'sky_tpu_lb_slo_alerts_firing', 'gauge'),
        'slo_burn': ('sky_tpu_lb_slo_burn', 'gauge'),
        # Cost plane (docs/cost.md).
        'fleet_cost_per_hour': (
            'sky_tpu_lb_fleet_cost_per_hour', 'gauge'),
        'cost_per_1k_good_tokens': (
            'sky_tpu_lb_cost_per_1k_good_tokens', 'gauge'),
        'spot_fraction': ('sky_tpu_lb_spot_fraction', 'gauge'),
        'cost_catalog_stale': (
            'sky_tpu_lb_cost_catalog_stale', 'gauge'),
        # Scale to zero (docs/cost.md "Scale to zero").
        'parked_requests': ('sky_tpu_lb_parked_requests', 'gauge'),
        'cold_starts_total': (
            'sky_tpu_lb_cold_starts_total', 'counter'),
        'cold_start_p50_s': (
            'sky_tpu_lb_cold_start_p50_seconds', 'gauge'),
        # Data-integrity plane (docs/robustness.md "Data integrity").
        'replicas_quarantined': (
            'sky_tpu_lb_replicas_quarantined', 'counter'),
        'probe_failures_total': (
            'sky_tpu_lb_probe_failures_total', 'counter'),
        'probe_interval_s': (
            'sky_tpu_lb_probe_interval_seconds', 'gauge'),
        # Disaggregated prefill/decode (docs/serving.md).
        'kv_transfers_total': (
            'sky_tpu_lb_kv_transfers_total', 'counter'),
        'kv_transfer_bytes': (
            'sky_tpu_lb_kv_transfer_bytes', 'counter'),
        'kv_transfer_failures': (
            'sky_tpu_lb_kv_transfer_failures', 'counter'),
        'kv_transfer_p99_s': (
            'sky_tpu_lb_kv_transfer_p99_seconds', 'gauge'),
        'fleet_prefix_hit_rate': (
            'sky_tpu_lb_fleet_prefix_hit_rate', 'gauge'),
        'fleet_prefix_pages': (
            'sky_tpu_lb_fleet_prefix_pages', 'gauge'),
    }


def replica_exposition() -> Dict[str, Tuple[str, str]]:
    """Scalar replica ``/metrics`` keys -> (family, type)."""
    return {
        'decode_steps': ('sky_tpu_engine_decode_steps', 'counter'),
        'decode_tokens': ('sky_tpu_engine_decode_tokens', 'counter'),
        'decode_tokens_per_sec': (
            'sky_tpu_engine_decode_tokens_per_sec', 'gauge'),
        'num_waiting': ('sky_tpu_engine_num_waiting', 'gauge'),
        'num_active': ('sky_tpu_engine_num_active', 'gauge'),
        'queued_tokens': ('sky_tpu_engine_queued_tokens', 'gauge'),
        'tokens_per_step': (
            'sky_tpu_engine_tokens_per_step', 'gauge'),
        'tokens_in_flight': (
            'sky_tpu_engine_tokens_in_flight', 'gauge'),
        'ttft_p50_s': ('sky_tpu_engine_ttft_p50_seconds', 'gauge'),
        'queue_wait_p50_ms': (
            'sky_tpu_engine_queue_wait_p50_ms', 'gauge'),
        'queue_wait_p99_ms': (
            'sky_tpu_engine_queue_wait_p99_ms', 'gauge'),
        'requests_abandoned': (
            'sky_tpu_engine_requests_abandoned', 'counter'),
        'requests_expired': (
            'sky_tpu_engine_requests_expired', 'counter'),
        'requests_cancelled': (
            'sky_tpu_engine_requests_cancelled', 'counter'),
        'requests_shed': ('sky_tpu_server_requests_shed', 'counter'),
        'server_inflight': ('sky_tpu_server_inflight', 'gauge'),
        'draining': ('sky_tpu_server_draining', 'gauge'),
        'prefill_tokens': (
            'sky_tpu_engine_prefill_tokens', 'counter'),
        'fused_steps': ('sky_tpu_engine_fused_steps', 'counter'),
        'decode_stall_steps': (
            'sky_tpu_engine_decode_stall_steps', 'counter'),
        'spec_steps': ('sky_tpu_engine_spec_steps', 'counter'),
        'spec_drafted_tokens': (
            'sky_tpu_engine_spec_drafted_tokens', 'counter'),
        'spec_accepted_tokens': (
            'sky_tpu_engine_spec_accepted_tokens', 'counter'),
        'spec_accept_rate': (
            'sky_tpu_engine_spec_accept_rate', 'gauge'),
        'accepted_len_mean': (
            'sky_tpu_engine_accepted_len_mean', 'gauge'),
        'pages_total': ('sky_tpu_engine_pages_total', 'gauge'),
        'pages_free': ('sky_tpu_engine_pages_free', 'gauge'),
        'preemptions': ('sky_tpu_engine_preemptions', 'counter'),
        'prefix_hit_rate': (
            'sky_tpu_engine_prefix_hit_rate', 'gauge'),
        'prefix_cached_pages': (
            'sky_tpu_engine_prefix_cached_pages', 'gauge'),
        'prefix_evictions': (
            'sky_tpu_engine_prefix_evictions', 'counter'),
        'stepline_steps': (
            'sky_tpu_engine_stepline_steps', 'counter'),
        'stepline_dumps': (
            'sky_tpu_engine_stepline_dumps', 'counter'),
        # The engine thread's wait for work, and what its launches
        # found on the device (docs/observability.md "The stages in a
        # profiler trace").
        'engine_wait_s': ('sky_tpu_engine_wait_seconds', 'counter'),
        'launches': ('sky_tpu_engine_launches', 'counter'),
        'launches_device_empty': (
            'sky_tpu_engine_launches_device_empty', 'counter'),
        'launches_after_wait': (
            'sky_tpu_engine_launches_after_wait', 'counter'),
        # How first tokens were read (docs/serving.md "The decode
        # pipeline").
        'first_token_total': (
            'sky_tpu_engine_first_token_total', 'counter'),
        'first_token_early_total': (
            'sky_tpu_engine_first_token_early_total', 'counter'),
        # Data-integrity plane (docs/robustness.md "Data integrity");
        # the string-valued ``integrity`` state renders as a labeled
        # state-set, not a scalar.
        'sdc_events_total': (
            'sky_tpu_engine_sdc_events_total', 'counter'),
        # Disaggregated prefill/decode (docs/serving.md).
        'kv_transfers_total': (
            'sky_tpu_engine_kv_transfers_total', 'counter'),
        'kv_transfer_bytes': (
            'sky_tpu_engine_kv_transfer_bytes', 'counter'),
        'kv_transfer_failures': (
            'sky_tpu_engine_kv_transfer_failures', 'counter'),
        'kv_transfer_p99_s': (
            'sky_tpu_engine_kv_transfer_p99_seconds', 'gauge'),
        'prefix_indexed_pages': (
            'sky_tpu_engine_prefix_indexed_pages', 'gauge'),
    }


def label_families() -> Dict[str, Tuple[str, str]]:
    """Labeled families (not scalar-key derived): logical name ->
    (family, type). The logical names pick the renderer branch; the
    family strings are what SKY-REGISTRY cross-checks."""
    return {
        'lb_tenant_requests_total': (
            'sky_tpu_lb_tenant_requests_total', 'counter'),
        'lb_tenant_requests_shed': (
            'sky_tpu_lb_tenant_requests_shed', 'counter'),
        'lb_tenant_requests_failed': (
            'sky_tpu_lb_tenant_requests_failed', 'counter'),
        'lb_tenant_ttft_p99': (
            'sky_tpu_lb_tenant_ttft_p99_seconds', 'gauge'),
        'lb_replica_queue_depth': (
            'sky_tpu_lb_replica_queue_depth', 'gauge'),
        'lb_breaker_state': ('sky_tpu_lb_breaker_state', 'gauge'),
        'lb_draining_replicas': (
            'sky_tpu_lb_draining_replicas', 'gauge'),
        'lb_quarantined_replicas': (
            'sky_tpu_lb_quarantined_replicas', 'gauge'),
        'engine_integrity': (
            'sky_tpu_engine_integrity_state', 'gauge'),
        'slo_burn_rate': ('sky_tpu_lb_slo_burn_rate', 'gauge'),
        'slo_budget': (
            'sky_tpu_lb_slo_error_budget_remaining', 'gauge'),
        'slo_firing': ('sky_tpu_lb_slo_alert_firing', 'gauge'),
        'engine_tenant_queue_depth': (
            'sky_tpu_engine_tenant_queue_depth', 'gauge'),
        'engine_tenant_decode_tokens': (
            'sky_tpu_engine_tenant_decode_tokens', 'counter'),
        'engine_tenant_requests_shed': (
            'sky_tpu_engine_tenant_requests_shed', 'counter'),
        'engine_tenant_ttft_p99': (
            'sky_tpu_engine_tenant_ttft_p99_seconds', 'gauge'),
    }


def _labels(pairs: Mapping[str, Any]) -> str:
    inner = ','.join(
        f'{k}="{store_lib.sanitize_label(v)}"'
        for k, v in sorted(pairs.items()))
    return '{' + inner + '}'


class _Doc:
    """Accumulates exposition samples grouped by family: the text
    format requires ALL lines of one family to form a single
    contiguous group under its # TYPE header, but the renderers
    iterate entity-major (per tenant, per replica, per objective) —
    so samples collect per family here and emit family-major, in
    first-add order, at ``text()`` time."""

    def __init__(self) -> None:
        # family -> (type, {label-suffix: value}); dicts preserve
        # insertion order, so families (and samples within one)
        # render in the order renderers add them.
        self._families: Dict[str, Tuple[str, Dict[str, Any]]] = {}

    def add(self, family: str, mtype: str, value: Any,
            labels: Optional[Mapping[str, Any]] = None) -> None:
        if value is None:
            return
        if isinstance(value, bool):
            value = int(value)
        if not isinstance(value, (int, float)):
            return
        group = self._families.get(family)
        if group is None:
            group = self._families[family] = (mtype, {})
        suffix = _labels(labels) if labels else ''
        # Post-sanitization label COLLISIONS (two tenant ids mapping
        # to one label value) must not emit duplicate series — a
        # scrape containing duplicates is rejected wholesale, a
        # client-triggerable observability outage. Counters fold by
        # sum (the collided series' true total); gauges keep the
        # first sample.
        if suffix in group[1]:
            if mtype == 'counter':
                group[1][suffix] += value
        else:
            group[1][suffix] = value

    def text(self) -> str:
        lines: List[str] = []
        for family, (mtype, samples) in self._families.items():
            lines.append(f'# TYPE {family} {mtype}')
            lines.extend(f'{family}{suffix} {value}'
                         for suffix, value in samples.items())
        return '\n'.join(lines) + '\n'


def _emit_scalars(doc: _Doc, metrics: Mapping[str, Any],
                  exposition: Dict[str, Tuple[str, str]]) -> None:
    for key, (family, mtype) in exposition.items():
        doc.add(family, mtype, metrics.get(key))


def render_lb(metrics: Dict[str, Any]) -> str:
    """The serve LB's ``lb_metrics()`` as Prometheus text."""
    doc = _Doc()
    fams = label_families()
    _emit_scalars(doc, metrics, lb_exposition())
    fam, t = fams['lb_draining_replicas']
    doc.add(fam, t, len(metrics.get('draining') or ()))
    fam, t = fams['lb_quarantined_replicas']
    doc.add(fam, t, len(metrics.get('quarantined') or ()))
    for tenant, row in sorted(
            (metrics.get('tenants') or {}).items()):
        labels = {'tenant': tenant}
        fam, t = fams['lb_tenant_requests_total']
        doc.add(fam, t, row.get('requests_total'), labels)
        fam, t = fams['lb_tenant_requests_shed']
        doc.add(fam, t, row.get('requests_shed'), labels)
        fam, t = fams['lb_tenant_requests_failed']
        doc.add(fam, t, row.get('requests_failed'), labels)
        fam, t = fams['lb_tenant_ttft_p99']
        doc.add(fam, t, row.get('ttft_p99_s'), labels)
    for url, depth in sorted(
            (metrics.get('replica_queue_depth') or {}).items()):
        fam, t = fams['lb_replica_queue_depth']
        doc.add(fam, t, depth, {'replica': url})
    for url, state in sorted((metrics.get('breaker') or {}).items()):
        # One series per (replica, state), value 1 for the active
        # state — the standard state-set encoding.
        fam, t = fams['lb_breaker_state']
        doc.add(fam, t, 1, {'replica': url, 'state': state})
    for key, row in sorted((metrics.get('slo') or {}).items()):
        labels = {'objective': key}
        fam, t = fams['slo_budget']
        doc.add(fam, t, row.get('error_budget_remaining'), labels)
        for tier in ('page', 'ticket'):
            for window in ('short', 'long'):
                fam, t = fams['slo_burn_rate']
                doc.add(fam, t, row.get(f'{tier}_burn_{window}'),
                        {**labels, 'tier': tier, 'window': window})
            fam, t = fams['slo_firing']
            doc.add(fam, t, row.get(f'{tier}_firing'),
                    {**labels, 'tier': tier})
    return doc.text()


def render_replica(metrics: Dict[str, Any]) -> str:
    """An inference replica's ``/metrics`` JSON as Prometheus text
    (EnginePool tiers stay JSON-only; the pool-level rollup is what
    the fleet scrape wants)."""
    doc = _Doc()
    fams = label_families()
    _emit_scalars(doc, metrics, replica_exposition())
    integ = metrics.get('integrity')
    if isinstance(integ, str):
        # State-set encoding (the breaker-state rule): one series per
        # state, value 1 for the active one — a string never survives
        # _Doc.add as a scalar.
        fam, t = fams['engine_integrity']
        doc.add(fam, t, 1, {'state': integ})
    for tenant, row in sorted(
            (metrics.get('tenants') or {}).items()):
        if not isinstance(row, dict):
            continue
        labels = {'tenant': tenant}
        fam, t = fams['engine_tenant_queue_depth']
        doc.add(fam, t, row.get('queue_depth'), labels)
        fam, t = fams['engine_tenant_decode_tokens']
        doc.add(fam, t, row.get('decode_tokens'), labels)
        fam, t = fams['engine_tenant_requests_shed']
        doc.add(fam, t, row.get('requests_shed'), labels)
        fam, t = fams['engine_tenant_ttft_p99']
        doc.add(fam, t, row.get('ttft_p99_s'), labels)
    return doc.text()
