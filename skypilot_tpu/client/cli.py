"""`sky-tpu` command-line interface.

Counterpart of the reference's click CLI (reference sky/client/cli/
command.py, 7,856 LoC). Commands call the engine directly when no API
server is configured, or go through the SDK/API server when
``SKY_TPU_API_SERVER`` is set (reference architecture: CLI → SDK → server;
the direct path matches the reference's early engine-only mode that
SURVEY.md §7 stage 4 recommends building first).
"""
from __future__ import annotations

import os
import sys
import time
from typing import Optional

import click

import skypilot_tpu as sky
from skypilot_tpu.utils import common


def _engine():
    """Engine facade: direct or via SDK depending on config."""
    if os.environ.get('SKY_TPU_API_SERVER'):
        try:
            from skypilot_tpu.client import sdk
        except ImportError as e:
            raise click.ClickException(
                f'SKY_TPU_API_SERVER is set but the SDK is unavailable: '
                f'{e}') from e
        sdk.ensure_server_compatibility()
        return sdk
    from skypilot_tpu import core
    return core


@click.group()
@click.version_option(sky.__version__)
def cli() -> None:
    """sky-tpu: TPU-native workload orchestrator."""


def _env_overrides(env: tuple) -> Optional[dict]:
    overrides = {}
    for e in env:
        k, _, v = e.partition('=')
        overrides[k] = v
    return overrides or None


def _load_task(yaml_path: str, env: tuple) -> 'sky.Task':
    return sky.Task.from_yaml(yaml_path, env_overrides=_env_overrides(env))


@cli.command()
@click.argument('task_yaml')
@click.option('--cluster', '-c', default=None, help='Cluster name.')
@click.option('--cloud', default=None, help='Override cloud.')
@click.option('--env', multiple=True, help='KEY=VALUE env override.')
@click.option('--detach-run', '-d', is_flag=True, default=False)
@click.option('--yes', '-y', is_flag=True, default=False)
@click.option('--down', 'autodown', is_flag=True, default=False,
              help='Autodown the cluster when the job finishes.')
def launch(task_yaml: str, cluster: Optional[str], cloud: Optional[str],
           env: tuple, detach_run: bool, yes: bool, autodown: bool) -> None:
    """Launch a task from a YAML spec (provision + run).

    Multi-document YAMLs describe a pipeline (serial chain) or a job
    group (``execution: parallel``) and run through the DAG path.
    """
    import yaml as yaml_lib
    with open(os.path.expanduser(task_yaml), encoding='utf-8') as f:
        docs = [d for d in yaml_lib.safe_load_all(f) if d is not None]
    if len(docs) > 1:
        from skypilot_tpu import execution
        from skypilot_tpu.utils import dag_utils
        overrides = dict(e.partition('=')[::2] for e in env)
        dag = dag_utils.load_dag_from_yaml(task_yaml,
                                           overrides or None)
        if cloud:
            for t in dag.tasks:
                t.set_resources(t.resources.copy(cloud=cloud))
        if cluster:
            click.echo('Warning: --cluster is ignored for multi-task '
                       'YAMLs (each task gets its own cluster).')
        if detach_run and not dag.is_job_group():
            click.echo('Warning: --detach-run is ignored for serial '
                       'pipelines (stages must run in order).')
        if not yes:
            mode = 'job group' if dag.is_job_group() else 'pipeline'
            click.confirm(
                f'Launching {mode} {dag.name or task_yaml} '
                f'({len(dag)} tasks). Proceed?', abort=True)
        results = execution.launch_dag(dag, quiet=False, down=autodown,
                                       detach_run=detach_run)
        for name, job_id, _ in results:
            click.echo(f'Cluster: {name}  job: {job_id}')
        return
    task = _load_task(task_yaml, env)
    if cloud:
        task.set_resources(task.resources.copy(cloud=cloud))
    if not yes:
        click.confirm(
            f'Launching {task.name or task_yaml} '
            f'({task.resources!r}, {task.num_nodes} host(s)). Proceed?',
            abort=True)
    engine = _engine()
    job_id, info = engine.launch(task, cluster_name=cluster, quiet=False)
    name = info.cluster_name
    click.echo(f'Cluster: {name}  job: {job_id}')
    if autodown:
        # Server-side: the agent downs the cluster once its queue idles —
        # works detached and survives a client crash mid-tail.
        engine.autostop(name, 0, True)
        click.echo(f'{name}: will autodown when idle.')
    if job_id >= 0 and not detach_run:
        for chunk in engine.tail_logs(name, job_id, follow=True):
            sys.stdout.buffer.write(chunk)
            sys.stdout.buffer.flush()
        st = engine.job_status(name, job_id)
        click.echo(f'Job {job_id}: {st.value}')
        if st != common.JobStatus.SUCCEEDED:
            sys.exit(100)


@cli.command('exec')
@click.argument('cluster')
@click.argument('task_yaml')
@click.option('--env', multiple=True)
@click.option('--detach-run', '-d', is_flag=True, default=False)
def exec_cmd(cluster: str, task_yaml: str, env: tuple,
             detach_run: bool) -> None:
    """Run a task on an existing cluster (skips provision/setup)."""
    task = _load_task(task_yaml, env)
    engine = _engine()
    job_id, _ = engine.exec(task, cluster)
    click.echo(f'Job: {job_id}')
    if not detach_run:
        for chunk in engine.tail_logs(cluster, job_id, follow=True):
            sys.stdout.buffer.write(chunk)
            sys.stdout.buffer.flush()


@cli.command()
@click.option('--refresh', '-r', is_flag=True, default=False)
@click.option('--all-workspaces', '-u', is_flag=True, default=False,
              help='Include clusters from every workspace.')
def status(refresh: bool, all_workspaces: bool) -> None:
    """Show clusters (scoped to the active workspace by default)."""
    records = _engine().status(refresh=refresh,
                               all_workspaces=all_workspaces)
    if not records:
        click.echo('No clusters.')
        return
    fmt = '{:<18} {:<10} {:<26} {:<8} {:<14}'
    click.echo(fmt.format('NAME', 'STATUS', 'RESOURCES', 'HOSTS',
                          'AUTOSTOP'))
    for r in records:
        res = r['resources']
        acc = res.get('accelerators') or res.get('instance_type', '-')
        hosts = len((r['cluster_info'] or {}).get('hosts', [])) or 1
        astop = (f"{r['autostop_minutes']}m"
                 f"{' (down)' if r['autostop_down'] else ''}"
                 if r['autostop_minutes'] >= 0 else '-')
        click.echo(fmt.format(r['name'], r['status'].value,
                              f"{res.get('cloud', '?')}:{acc}", hosts,
                              astop))


@cli.command()
@click.argument('cluster')
@click.argument('job_id', type=int)
@click.option('--no-follow', is_flag=True, default=False)
@click.option('--rank', type=int, default=0,
              help='Which host rank log to stream.')
def logs(cluster: str, job_id: int, no_follow: bool, rank: int) -> None:
    """Stream a job's logs."""
    for chunk in _engine().tail_logs(cluster, job_id,
                                     follow=not no_follow, rank=rank):
        sys.stdout.buffer.write(chunk)
        sys.stdout.buffer.flush()


@cli.command()
@click.argument('cluster')
def queue(cluster: str) -> None:
    """Show a cluster's job queue."""
    jobs = _engine().queue(cluster)
    fmt = '{:<6} {:<16} {:<12} {:<8}'
    click.echo(fmt.format('ID', 'NAME', 'STATUS', 'HOSTS'))
    for j in jobs:
        click.echo(fmt.format(j['job_id'], j['name'], j['status'],
                              j['num_hosts']))


@cli.command()
@click.argument('cluster')
@click.argument('job_id', type=int)
def cancel(cluster: str, job_id: int) -> None:
    """Cancel a job."""
    _engine().cancel(cluster, job_id)
    click.echo(f'Cancelled job {job_id} on {cluster}.')


@cli.command()
@click.argument('cluster')
@click.option('--yes', '-y', is_flag=True, default=False)
def stop(cluster: str, yes: bool) -> None:
    """Stop a cluster (keep disk)."""
    if not yes:
        click.confirm(f'Stop cluster {cluster}?', abort=True)
    _engine().stop(cluster)
    click.echo(f'Cluster {cluster} stopped.')


@cli.command()
@click.argument('cluster')
def start(cluster: str) -> None:
    """Restart a stopped cluster."""
    _engine().start(cluster)
    click.echo(f'Cluster {cluster} started.')


@cli.command()
@click.argument('cluster')
@click.option('--yes', '-y', is_flag=True, default=False)
def down(cluster: str, yes: bool) -> None:
    """Terminate a cluster."""
    if not yes:
        click.confirm(f'Terminate cluster {cluster}?', abort=True)
    _engine().down(cluster)
    click.echo(f'Cluster {cluster} terminated.')


@cli.command()
@click.argument('cluster')
@click.option('--idle-minutes', '-i', type=int, required=True)
@click.option('--down', 'down_', is_flag=True, default=False)
def autostop(cluster: str, idle_minutes: int, down_: bool) -> None:
    """Set autostop/autodown after idleness."""
    _engine().autostop(cluster, idle_minutes, down_)
    click.echo(f'{cluster}: autostop {idle_minutes}m'
               f'{" then down" if down_ else ""}.')


@cli.command()
def check() -> None:
    """Probe cloud credentials and capabilities."""
    engine = _engine()
    if not hasattr(engine, 'check_detailed'):
        # Remote SDK path: the API server probes ITS credentials and
        # records enabled clouds in its own state DB.
        for cloud, ok in engine.check().items():
            click.echo(f'  {"✓" if ok else "✗"} {cloud}: '
                       f'{"enabled" if ok else "disabled"}')
        return
    results = engine.check_detailed()
    for r in results:
        mark = '✓' if r.ok else '✗'
        line = f'  {mark} {r.cloud}: {"enabled" if r.ok else "disabled"}'
        if r.ok and r.storage_ok:
            line += ' [compute, storage]'
        elif r.ok:
            line += ' [compute]'
        click.echo(line)
        if r.reason:
            click.echo(f'      {r.reason}')
        for k, v in r.details.items():
            click.echo(f'      {k}: {v}')
    enabled = [r.cloud for r in results if r.ok]
    click.echo(f'\nEnabled clouds: {", ".join(enabled) or "none"}')


@cli.command('trace')
@click.argument('request_id', required=False)
@click.option('--perfetto', 'perfetto_path', default=None,
              help='Also write Perfetto/Chrome-trace JSON here '
                   '(open in ui.perfetto.dev or chrome://tracing).')
def trace_cmd(request_id: Optional[str],
              perfetto_path: Optional[str]) -> None:
    """Render the distributed trace of one API request.

    REQUEST_ID is the id `sky-tpu` ops return (also accepts a raw
    trace id). With no argument, lists recent traces. Requires the
    request to have run with SKY_TPU_TRACE=1 on the client and server
    (see docs/observability.md).
    """
    import json as json_lib

    from skypilot_tpu.observability import render as render_lib
    from skypilot_tpu.observability import store as store_lib
    from skypilot_tpu.observability import trace as trace_mod

    def _local_store():
        return store_lib.SpanStore()

    # Query wherever spans actually shipped: the same resolution chain
    # the shipper uses (env → config endpoint → local api_server.json),
    # falling back to the client-local store. The resolved URL is
    # pinned into the env so the SDK talks to the SAME server (a local
    # server found via api_server.json may sit on a non-default port).
    server = trace_mod._resolve_collector()  # noqa: SLF001
    use_server = server is not None
    if use_server:
        os.environ['SKY_TPU_API_SERVER'] = server
    if request_id is None:
        traces = None
        if use_server:
            from skypilot_tpu import exceptions as exc
            from skypilot_tpu.client import sdk
            try:
                traces = sdk.api_traces()
            except exc.SkyTpuError:
                traces = None   # stale/dead server: fall back to local
        if traces is None:
            traces = _local_store().list_traces()
        if not traces:
            click.echo('No traces recorded. Run with SKY_TPU_TRACE=1.')
            return
        fmt = '{:34} {:>8} {:24} {}'
        click.echo(fmt.format('TRACE', 'SPANS', 'ROOT', 'REQUEST'))
        for t in traces:
            click.echo(fmt.format(t['trace_id'], t['n_spans'],
                                  t.get('root') or '-',
                                  t.get('request_id') or '-'))
        return
    spans = []
    if use_server:
        from skypilot_tpu import exceptions as exc
        from skypilot_tpu.client import sdk
        try:
            spans = sdk.api_trace(request_id)
        except exc.SkyTpuError:
            spans = []
    if not spans:
        # Engine mode / server unreachable: the local span store holds
        # whatever this host's processes shipped.
        store = _local_store()
        spans = store.trace_for_request(request_id)
        if not spans:
            spans = store.get_trace(request_id)
    if not spans:
        raise click.ClickException(
            f'no trace recorded for {request_id!r} — run the request '
            f'with SKY_TPU_TRACE=1 (client and server), or check '
            f'`sky-tpu trace` for the trace list.')
    click.echo(render_lib.render_tree(spans))
    if perfetto_path:
        with open(perfetto_path, 'w', encoding='utf-8') as f:
            json_lib.dump(render_lib.to_perfetto(spans), f)
        click.echo(f'wrote {perfetto_path}')


def _fetch_json(url: str, timeout: float = 10.0):
    """GET + parse a control endpoint's JSON, converting transport
    and parse errors into one friendly ClickException (ValueError
    covers a non-JSON body, HTTPException a non-HTTP peer — wrong
    port, a reverse proxy's HTML error page)."""
    import http.client
    import json as json_lib
    import urllib.request
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return json_lib.loads(r.read())
    except (OSError, ValueError, http.client.HTTPException) as e:
        raise click.ClickException(f'could not fetch {url}: {e}')


@cli.command('profile')
@click.argument('target', required=False)
@click.option('--perfetto', 'perfetto_path', default=None,
              help='Write a Perfetto/Chrome-trace JSON of the '
                   'timeline (open in ui.perfetto.dev).')
@click.option('--steps', 'n_steps', default=20, show_default=True,
              help='Step records shown in the text summary.')
def profile_cmd(target: Optional[str], perfetto_path: Optional[str],
                n_steps: int) -> None:
    """Read the engine flight recorder (docs/observability.md
    "Flight recorder").

    TARGET is a replica URL (``http://host:port`` — fetches the live
    ``/debug/stepline`` ring) or a request id / dump trace id (reads
    the anomaly dumps the recorder snapshotted into the span store).
    With no argument, lists recorded dumps.
    """
    import json as json_lib

    from skypilot_tpu.observability import render as render_lib
    from skypilot_tpu.observability import stepline as stepline_lib
    from skypilot_tpu.observability import store as store_lib

    def _write_perfetto(make_doc) -> None:
        """``make_doc`` is a thunk: a full ring renders to tens of
        thousands of trace events — built only when --perfetto
        actually asked for them."""
        if not perfetto_path:
            return
        doc = make_doc()
        errs = stepline_lib.validate_perfetto(doc)
        if errs:
            raise click.ClickException(
                f'exported trace failed validation: {errs[:3]}')
        with open(perfetto_path, 'w', encoding='utf-8') as f:
            json_lib.dump(doc, f)
        click.echo(f'wrote {perfetto_path}')

    if target and target.startswith(('http://', 'https://')):
        snap = _fetch_json(target.rstrip('/') + '/debug/stepline')
        # Tolerate a replica on an older build whose records miss a
        # newer field — a version skew must degrade to zeros, not a
        # KeyError traceback.
        _defaults = {'kind': '?', 'tenant_depths': None}
        summ = stepline_lib.summarize([
            stepline_lib.StepRecord(**{
                k: rec.get(k, _defaults.get(k, 0))
                for k in stepline_lib.StepRecord.__slots__})
            for rec in snap.get('steps', ())])
        click.echo(f"steps recorded: {snap.get('steps_total', 0)} "
                   f"(ring keeps {len(snap.get('steps', []))}); "
                   f"anomaly dumps: {snap.get('dumps', 0)}")
        if summ['steps']:
            click.echo(
                'step time: mean {:.3f} ms — dispatch {:.0%}, drain '
                '{:.0%}, readback {:.0%}, host {:.0%}'.format(
                    summ['step_mean_ms'],
                    summ['dispatch_share'] or 0,
                    summ['drain_share'] or 0,
                    summ['readback_share'] or 0,
                    summ['host_share'] or 0))
            click.echo(
                'engine thread: on the CPU {:.0%} of the steps\' time; '
                'waited for work {:.3f} s beside {:.3f} s of steps '
                '({:.0%}); {} step(s) launched onto an empty '
                'device'.format(
                    summ['cpu_share'] or 0, summ['wait_s'],
                    summ['step_time_s'], summ['wait_share'] or 0,
                    summ['dev_empty_steps']))
            click.echo(f"step kinds: {summ['step_kinds']}")
            fmt = '{:>8} {:>8} {:>9} {:>9} {:>9} {:>6} {:>7} {:>7} {:>7}'
            click.echo(fmt.format('STEP', 'KIND', 'DUR_MS', 'CPU_MS',
                                  'WAIT_MS', 'BATCH', 'CHUNK', 'QUEUE',
                                  'FREEPG'))
            for rec in snap.get('steps', [])[-max(1, n_steps):]:
                click.echo(fmt.format(
                    rec.get('idx', 0), rec.get('kind', '?'),
                    f"{rec.get('dur_s', 0) * 1e3:.2f}",
                    f"{rec.get('cpu_s', 0) * 1e3:.2f}",
                    f"{rec.get('wait_s', 0) * 1e3:.2f}",
                    rec.get('batch', 0), rec.get('chunk_tokens', 0),
                    rec.get('queue_depth', 0),
                    rec.get('pages_free', -1)))
        _write_perfetto(lambda: stepline_lib.to_perfetto(snap))
        return

    store = store_lib.SpanStore()
    if not target:
        dumps = store.list_traces(limit=200,
                                  trace_id_prefix='stepline-')
        if not dumps:
            click.echo(
                'No flight-recorder dumps. Dumps appear after an '
                'anomaly (TTFT-SLO breach, preemption, cache_full, '
                'admission shed, breaker open); profile a live '
                'replica with `sky-tpu profile <url>`.')
            return
        fmt = '{:36} {:>8} {}'
        click.echo(fmt.format('DUMP', 'SPANS', 'REQUEST'))
        for t in dumps:
            click.echo(fmt.format(t['trace_id'], t['n_spans'],
                                  t.get('request_id') or '-'))
        return
    # A request id can live in both its ordinary PR-1 span trace and
    # a recorder dump; `profile` reads the black box, so prefer the
    # newest stepline-* trace and never silently render the plain
    # request trace (`sky-tpu trace` is the command for that).
    spans: list = []
    for tid in store.trace_ids_for_request(target):
        if str(tid).startswith('stepline-'):
            spans = store.get_trace(tid)
            break
    if not spans:
        spans = store.get_trace(target)
    spans = [s for s in spans or []]
    if not spans:
        raise click.ClickException(
            f'no flight-recorder dump for {target!r} — run '
            f'`sky-tpu profile` for the dump list, or profile a '
            f'live replica with its URL.')
    trigger = next((s for s in spans
                    if s['name'] == 'stepline.trigger'), None)
    if trigger is not None:
        click.echo(f"trigger: {trigger['status']} "
                   f"{trigger.get('attrs') or {}}")
    click.echo(render_lib.render_tree(spans))
    _write_perfetto(lambda: render_lib.to_perfetto(spans))


@cli.command('slo')
@click.argument('lb_url')
@click.option('--json', 'as_json', is_flag=True,
              help='Raw /-/alerts JSON instead of the table.')
def slo_cmd(lb_url: str, as_json: bool) -> None:
    """Show a live LB's SLO objectives, error budgets, and firing
    alerts (docs/observability.md "SLOs and alerting").

    LB_URL is the service endpoint (``http://host:port``); this reads
    its ``/-/alerts`` view: per-objective burn rates on the page
    (5m/1h) and ticket (30m/6h) windows, the error budget remaining,
    and the live firing set with recent transitions.
    """
    import json as json_lib

    doc = _fetch_json(lb_url.rstrip('/') + '/-/alerts')
    if as_json:
        click.echo(json_lib.dumps(doc, indent=1))
        return
    if not doc.get('enabled', False):
        click.echo('No SLO objectives declared for this service — '
                   'add an `slo:` section to the service spec '
                   '(docs/observability.md "SLOs and alerting").')
        return
    fmt = ('{:<24} {:<20} {:>7} {:>8} {:>9} {:>9} {:>8}')
    click.echo(fmt.format('OBJECTIVE', 'METRIC', 'TARGET', 'BUDGET',
                          'PAGE_5M', 'PAGE_1H', 'STATE'))
    for key, row in sorted(doc.get('objectives', {}).items()):
        state = ('PAGE' if row.get('page_firing')
                 else 'ticket' if row.get('ticket_firing') else 'ok')
        metric = row.get('metric', '?')
        if row.get('threshold_s') is not None:
            metric += f"<={row['threshold_s']:g}s"
        if row.get('tenant'):
            metric += f" [{row['tenant']}]"
        click.echo(fmt.format(
            key, metric, f"{row.get('target', 0):g}",
            f"{row.get('error_budget_remaining', 0):.2%}",
            f"{row.get('page_burn_short', 0):g}",
            f"{row.get('page_burn_long', 0):g}", state))
    firing = doc.get('firing') or []
    if firing:
        click.echo('\nFIRING:')
        for f in firing:
            click.echo(f"  [{f['tier']}] {f['objective']} "
                       f"since t={f.get('since_t')}")
    tail = (doc.get('transitions') or [])[-5:]
    if tail:
        click.echo('\nrecent transitions:')
        for t in tail:
            click.echo(f"  t={t['t']} {t['tier']} {t['objective']} "
                       f"-> {t['state']} (burn {t['burn_short']}/"
                       f"{t['burn_long']})")


@cli.command('cost')
@click.argument('lb_url')
@click.option('--json', 'as_json', is_flag=True,
              help='Raw cost keys of /-/metrics instead of the '
                   'report.')
def cost_cmd(lb_url: str, as_json: bool) -> None:
    """Show a live service's fleet cost report (docs/cost.md
    "Reading a cost report").

    LB_URL is the service endpoint (``http://host:port``); this reads
    the cost-plane keys of its ``/-/metrics`` view: the fleet's
    current $/hour and spot fraction (from the controller's catalog
    snapshot), the efficiency rate in $ per 1k good tokens, and the
    scale-to-zero counters (parked requests, cold starts).
    """
    import json as json_lib

    m = _fetch_json(lb_url.rstrip('/') + '/-/metrics')
    keys = ('fleet_cost_per_hour', 'cost_per_1k_good_tokens',
            'spot_fraction', 'cost_catalog_stale', 'parked_requests',
            'cold_starts_total', 'cold_start_p50_s')
    if as_json:
        click.echo(json_lib.dumps({k: m.get(k) for k in keys},
                                  indent=1))
        return
    rate = m.get('fleet_cost_per_hour') or 0.0
    per_1k = m.get('cost_per_1k_good_tokens')
    click.echo(f'fleet cost:      ${rate:.4f}/hour '
               f'(${rate * 24 * 30:.2f}/month at this rate)')
    click.echo('cost efficiency: '
               + (f'${per_1k:.6f} per 1k good tokens'
                  if per_1k is not None else
                  'n/a (no recent token throughput)'))
    click.echo(f"spot fraction:   {m.get('spot_fraction', 0.0):.0%} "
               f"of {m.get('ready_replicas', 0)} ready replica(s)")
    if m.get('cost_catalog_stale'):
        click.echo('WARNING: price catalog is STALE — placement is '
                   'running on last-known prices (the fetcher is '
                   'failing; see serve.costplane.catalog_stale).')
    cold = m.get('cold_starts_total') or 0
    if cold or m.get('parked_requests'):
        p50 = m.get('cold_start_p50_s')
        click.echo(f"scale-to-zero:   {m.get('parked_requests', 0)} "
                   f'parked request(s), {cold} cold start(s)'
                   + (f', p50 wake {p50:.1f}s'
                      if p50 is not None else ''))


@cli.group('incident')
def incident() -> None:
    """Incident replay plane (docs/simulation.md): convert
    flight-recorder anomaly dumps into replayable twin scenarios."""


@incident.command('list')
def incident_list() -> None:
    """List exportable flight-recorder dumps in the span store."""
    from skypilot_tpu.observability import incident as incident_lib
    from skypilot_tpu.observability import store as store_lib

    dumps = incident_lib.list_dumps(store_lib.SpanStore())
    if not dumps:
        click.echo('No flight-recorder dumps. Dumps appear after an '
                   'anomaly (slo_page, breaker_open, quarantine, '
                   'engine stepline triggers).')
        return
    fmt = '{:36} {:14} {:>8}'
    click.echo(fmt.format('DUMP', 'TRIGGER', 'SPANS'))
    for d in dumps:
        click.echo(fmt.format(d['dump_id'], d['trigger'] or '-',
                              d['n_spans']))


@incident.command('export')
@click.argument('dump_id')
@click.option('--output', '-o', default=None,
              help='Incident trace path (default '
                   '<dump-id>.incident.jsonl).')
def incident_export(dump_id: str, output: Optional[str]) -> None:
    """Export a flight-recorder dump as a versioned incident trace.

    DUMP_ID is a span-store dump trace id (or unique prefix) from
    `sky-tpu incident list` / `sky-tpu profile`. The exported JSONL
    carries the reconstructed arrival process and inferred fault
    timeline, scrubbed to lengths + cohort hashes — no prompt
    content. Replay it with `sky-tpu incident replay` or commit it
    under tests/sim/incidents/ as a permanent regression gate.
    """
    from skypilot_tpu.observability import incident as incident_lib
    from skypilot_tpu.observability import store as store_lib

    try:
        trace = incident_lib.trace_from_spans(
            incident_lib.find_dump(store_lib.SpanStore(), dump_id))
    except ValueError as e:
        raise click.ClickException(str(e))
    path = output or f"{trace.meta.get('dump_id', dump_id)}" \
                     f'.incident.jsonl'
    from skypilot_tpu.sim import tracefmt
    tracefmt.save(trace, path)
    click.echo(f'wrote {path}: trigger='
               f"{trace.meta.get('trigger')}, "
               f'{len(trace.requests)} request(s), '
               f'{len(trace.faults)} fault(s), '
               f'{len(trace.kills)} kill(s)')
    if trace.truncated:
        # No-silent-caps: a wrapped evidence ring makes a PARTIAL
        # incident — say exactly how much history fell off.
        click.echo(
            f'WARNING: evidence rings wrapped before the dump — '
            f"{trace.meta.get('dropped_request_events', 0)} request "
            f'event(s) and '
            f"{trace.meta.get('dropped_fleet_events', 0)} fleet "
            f'event(s) fell off; the trace is marked '
            f'truncated: true')


@incident.command('replay')
@click.argument('trace_file')
@click.option('--seed', default=0, show_default=True)
@click.option('--json', 'as_json', is_flag=True,
              help='Machine-readable verdict JSON.')
def incident_replay(trace_file: str, seed: int,
                    as_json: bool) -> None:
    """Replay an exported incident in the digital twin and verify the
    recorded anomaly class reproduces (same page-alert sequence)."""
    import json as json_lib

    from skypilot_tpu.observability import incident as incident_lib
    from skypilot_tpu.sim import tracefmt

    try:
        trace = tracefmt.load(trace_file)
    except ValueError as e:
        raise click.ClickException(str(e))
    report = incident_lib.replay(trace, seed=seed)
    problems = incident_lib.verify_replay(trace, report)
    if as_json:
        click.echo(json_lib.dumps({
            'reproduced': not problems, 'problems': problems,
            'recorded_page_firing':
                trace.meta.get('expected_page_firing') or [],
            'summary': report.summary()}, indent=1, sort_keys=True))
    else:
        click.echo(f'replayed {len(report.records)} request(s), '
                   f'{len(report.slo_alerts)} alert transition(s)')
        for p in problems:
            click.echo(f'PROBLEM: {p}')
        click.echo('reproduced: ' + ('yes' if not problems else 'NO'))
    if problems:
        sys.exit(1)


@cli.command('simulate')
@click.option('--spec', 'spec_path', default=None,
              help='Service YAML whose replica_policy/'
                   'load_balancing_policy/slo sections override the '
                   "trace's recorded config (optional `sim:` section "
                   'for twin-only knobs).')
@click.option('--trace', 'trace_path', required=True,
              help='Trace file: a loadgen trace (replayed verbatim) '
                   'or an exported incident (arrival process + fault '
                   'timeline reconstruction).')
@click.option('--seed', default=0, show_default=True)
@click.option('--sweep', 'sweep_arg', default=None,
              help='One-knob sweep key=v1,v2,... over Scenario '
                   'fields (e.g. slots=4,8 or lb_sync_s=5,15); '
                   'emits a ranked table with per-run decision-log '
                   'digests.')
@click.option('--json', 'as_json', is_flag=True,
              help='Raw summary JSON instead of the report.')
def simulate_cmd(spec_path: Optional[str], trace_path: str,
                 seed: int, sweep_arg: Optional[str],
                 as_json: bool) -> None:
    """What-if simulation (docs/simulation.md): run a recorded trace
    through the digital twin headless and report SLO burn, shed/
    resume/quarantine counts, autoscaler churn, and metered cost —
    deterministically per seed."""
    import json as json_lib

    from skypilot_tpu.sim import tracefmt
    from skypilot_tpu.sim import whatif

    try:
        trace = tracefmt.load(trace_path)
    except ValueError as e:
        raise click.ClickException(str(e))
    spec: dict = {}
    if spec_path:
        import yaml as yaml_lib
        with open(os.path.expanduser(spec_path),
                  encoding='utf-8') as f:
            doc = yaml_lib.safe_load(f) or {}
        spec = doc.get('service') or doc
    try:
        scenario = whatif.scenario_from_spec(spec, trace)
        if sweep_arg:
            key, values = whatif.parse_sweep(sweep_arg)
            rows = whatif.run_sweep(scenario, key, values, seed=seed)
            if as_json:
                click.echo(json_lib.dumps(rows, indent=1,
                                          sort_keys=True))
            else:
                click.echo(whatif.sweep_table(rows))
            return
        summary = whatif.run_simulate(scenario, seed=seed)
    except ValueError as e:
        raise click.ClickException(str(e))
    if as_json:
        click.echo(json_lib.dumps(summary, indent=1, sort_keys=True))
        return
    click.echo(f"scenario {summary['scenario']} @ seed {seed}: "
               f"{summary['requests']} request(s), "
               f"{summary['completed']} completed, "
               f"{summary['shed']} shed, "
               f"{summary['client_errors']} client error(s), "
               f"{summary['resumed']} resumed, "
               f"{summary['quarantines']} quarantine(s)")
    slo = summary['slo']
    click.echo(f"SLO: page firing {slo['page_firing'] or 'none'}; "
               f"alerts by tier {slo['alerts_by_tier'] or '{}'}")
    auto = summary['autoscaler']
    click.echo(f"autoscaler: {auto['launches']} launch(es), "
               f"{auto['drains']} drain(s), churn {auto['churn']} "
               f"over targets {auto['targets'] or '[]'}")
    if summary['cost']:
        click.echo(f"cost: {summary['cost']}")
    click.echo(f"ttft: p50 {summary['ttft_p50_s']} "
               f"p99 {summary['ttft_p99_s']}")
    click.echo(f"decision log sha256: "
               f"{summary['decision_log_sha256']}")


@cli.command('show-accelerators')
@click.option('--filter', 'name_filter', default=None)
def show_accelerators(name_filter: Optional[str]) -> None:
    """List accelerators with pricing."""
    from skypilot_tpu import catalog
    accs = catalog.list_accelerators(name_filter=name_filter)
    fmt = '{:<12} {:<8} {:<6} {:<10} {:>10} {:>10}'
    click.echo(fmt.format('ACCELERATOR', 'CLOUD', 'HOSTS', 'TOPOLOGY',
                          '$/HR', 'SPOT $/HR'))
    for name in sorted(accs):
        for o in accs[name]:
            click.echo(fmt.format(
                name, o['cloud'], o.get('num_hosts', 1),
                o.get('topology', '-'),
                f"{o['price']:.2f}", f"{o['spot_price']:.2f}"))


@cli.command('cost-report')
def cost_report() -> None:
    """Cost of terminated clusters."""
    rows = _engine().cost_report()
    fmt = '{:<18} {:>10} {:>10}'
    click.echo(fmt.format('CLUSTER', 'HOURS', 'COST $'))
    for r in rows:
        click.echo(fmt.format(r['name'], f"{r['duration_hours']:.2f}",
                              f"{r['cost']:.2f}"))


def _changed_lint_paths() -> frozenset:
    """Package-relative paths of files changed vs git (worktree diff
    against HEAD + untracked), for `sky-tpu lint --changed`."""
    import subprocess

    import skypilot_tpu
    pkg_root = os.path.dirname(os.path.abspath(skypilot_tpu.__file__))
    repo_root = os.path.dirname(pkg_root)
    pkg_name = os.path.basename(pkg_root)
    try:
        diff = subprocess.run(
            ['git', '-C', repo_root, 'diff', '--name-only', 'HEAD'],
            capture_output=True, text=True, check=True)
        untracked = subprocess.run(
            ['git', '-C', repo_root, 'ls-files', '--others',
             '--exclude-standard'],
            capture_output=True, text=True, check=True)
    except (OSError, subprocess.CalledProcessError) as e:
        detail = getattr(e, 'stderr', '') or str(e)
        raise click.ClickException(
            f'--changed needs a git worktree: {detail.strip()}') from e
    out = set()
    for line in (diff.stdout + untracked.stdout).splitlines():
        line = line.strip()
        if line.startswith(f'{pkg_name}/') and line.endswith('.py'):
            out.add(line[len(pkg_name) + 1:])
        elif line.startswith('docs/') and line.endswith('.md'):
            out.add(line)
    return frozenset(out)


@cli.command('lint')
@click.argument('path', required=False)
@click.option('--json', 'as_json', is_flag=True, default=False,
              help='Machine-readable report (findings, offenders, '
                   'stale allowlist entries).')
@click.option('--verbose', '-v', is_flag=True, default=False,
              help='Also list allowlisted findings.')
@click.option('--no-allowlist', is_flag=True, default=False,
              help='Ignore the audited allowlist: report, and fail '
                   'on, every finding.')
@click.option('--changed', is_flag=True, default=False,
              help='Report only findings in files changed vs git '
                   '(diff against HEAD + untracked). The whole '
                   'package is still parsed — the interprocedural '
                   'passes need the full call graph — but the '
                   'parsed-module cache makes the re-scan cheap.')
def lint_cmd(path: Optional[str], as_json: bool, verbose: bool,
             no_allowlist: bool, changed: bool) -> None:
    """Run the AST-based invariant checkers over the package.

    Checkers (docs/static-analysis.md): SKY-LOCK (guarded-field lock
    discipline, interprocedural: `# holds:` annotations verified
    against real callers), SKY-ORDER (global lock-acquisition-order
    cycles + re-entrant non-reentrant acquisition), SKY-HOLD (no
    blocking operations — await/sleep/net/subprocess/device readback —
    while a lock is held), SKY-ASYNC (no blocking calls / sleep-polls
    in async and hot paths), SKY-EXCEPT (no swallowed reset/
    cancellation in serve/infer network paths), SKY-TRACE (no
    concretization or data-dependent branching in jit-reachable
    code), SKY-REGISTRY (failpoint sites + serving-metric keys in
    sync with the docs catalogs). PATH narrows the scan to one file
    or subtree (default: the whole installed package);
    ``--changed`` scopes the REPORT to git-changed files instead.
    Exits non-zero on any error-severity finding beyond the audited
    allowlist, or on a stale allowlist entry.
    """
    from skypilot_tpu import analysis
    report_paths = None
    if changed:
        if path:
            raise click.ClickException(
                'PATH and --changed are mutually exclusive')
        report_paths = _changed_lint_paths()
        if not report_paths:
            click.echo('lint --changed: no changed package files.')
            return
    try:
        report = analysis.run(
            root=path, allowlist={} if no_allowlist else None,
            report_paths=report_paths)
    except FileNotFoundError as e:
        raise click.ClickException(str(e)) from e
    if as_json:
        click.echo(report.to_json())
    else:
        click.echo(report.render_text(verbose=verbose))
    if not report.ok:
        sys.exit(1)


@cli.group()
def jobs() -> None:
    """Managed jobs: auto-recovering (spot) task execution."""


def _jobs_engine():
    """jobs facade: direct engine or SDK (mirrors _engine())."""
    if os.environ.get('SKY_TPU_API_SERVER'):
        from skypilot_tpu.client import sdk

        class _SdkJobs:
            launch = staticmethod(
                lambda task, name=None, pool=None:
                sdk.jobs_launch(task, name, pool=pool))
            queue = staticmethod(sdk.jobs_queue)
            cancel = staticmethod(sdk.jobs_cancel)
            pool_apply = staticmethod(sdk.jobs_pool_apply)
            pool_status = staticmethod(sdk.jobs_pool_status)
            pool_down = staticmethod(sdk.jobs_pool_down)
        return _SdkJobs
    from skypilot_tpu import jobs as jobs_lib
    return jobs_lib


@jobs.command('launch')
@click.argument('task_yaml', required=False)
@click.option('--recipe', default=None,
              help='Launch a stored recipe instead of a YAML file '
                   '(pipelines supported).')
@click.option('--name', '-n', default=None, help='Job name.')
@click.option('--pool', '-p', default=None,
              help='Run on a claimed worker from this pre-provisioned '
                   'pool instead of provisioning a cluster '
                   '(sky-tpu jobs pool apply).')
@click.option('--env', multiple=True, help='KEY=VALUE env override.')
@click.option('--yes', '-y', is_flag=True, default=False)
def jobs_launch(task_yaml: Optional[str], recipe: Optional[str],
                name: Optional[str], pool: Optional[str], env: tuple,
                yes: bool) -> None:
    """Submit a managed job (auto-recovers on preemption).

    A multi-document YAML submits a managed PIPELINE: stages run
    sequentially, each with its own cluster and per-stage recovery.
    --recipe NAME launches a stored template (sky-tpu recipe ls).
    --pool NAME runs on an idle worker of a pre-provisioned pool.
    """
    from skypilot_tpu.utils import dag_utils
    if (task_yaml is None) == (recipe is None):
        raise click.UsageError('pass exactly one of TASK_YAML or '
                               '--recipe NAME')
    if recipe:
        if _remote():
            from skypilot_tpu.client import sdk
            rec = sdk.call('recipes.get', {'name': recipe})
        else:
            from skypilot_tpu import recipes as recipes_lib
            rec = recipes_lib.get(recipe)
        dag = dag_utils.load_dag_from_yaml_str(
            rec['yaml'], env_overrides=_env_overrides(env))
        name = name or recipe
    else:
        dag = dag_utils.load_dag_from_yaml(
            task_yaml, env_overrides=_env_overrides(env))
    if len(dag) > 1:
        stages = ', '.join(t.name or f'stage-{i}'
                           for i, t in enumerate(dag.tasks))
        if not yes:
            click.confirm(
                f'Submitting managed pipeline '
                f'{name or dag.name or task_yaml} '
                f'({len(dag)} stages: {stages}). Proceed?', abort=True)
        job_id = _jobs_engine().launch(dag, name=name, pool=pool)
    else:
        task = dag.tasks[0]
        if not yes:
            where = (f'pool {pool}' if pool
                     else repr(task.resources))
            click.confirm(
                f'Submitting managed job {name or task.name or task_yaml} '
                f'({where}). Proceed?', abort=True)
        job_id = _jobs_engine().launch(task, name=name, pool=pool)
    click.echo(f'Managed job: {job_id}')
    click.echo(f'Watch: sky-tpu jobs queue   '
               f'logs: sky-tpu jobs logs {job_id}')


@jobs.group('pool')
def jobs_pool() -> None:
    """Worker pools: pre-provisioned clusters that managed jobs reuse."""


@jobs_pool.command('apply')
@click.argument('pool_yaml', required=False)
@click.option('--pool', '-p', 'pool_name', default=None,
              help='Pool name (defaults to the task name).')
@click.option('--workers', type=int, default=None,
              help='Override (or, without YAML, resize to) this many '
                   'workers.')
@click.option('--yes', '-y', is_flag=True, default=False)
def jobs_pool_apply_cmd(pool_yaml: Optional[str],
                        pool_name: Optional[str],
                        workers: Optional[int], yes: bool) -> None:
    """Create/update a worker pool from YAML, or resize with --workers.

    The YAML needs a `pool:` section (pool: {workers: N}) instead of
    `service:`; `setup:` pre-bakes each worker once, and jobs launched
    with `--pool NAME` bring their own `run` command.
    """
    task = None
    if pool_yaml is not None:
        from skypilot_tpu import task as task_lib
        task = task_lib.Task.from_yaml(pool_yaml)
    elif workers is None or pool_name is None:
        raise click.UsageError('pass POOL_YAML, or both --pool NAME and '
                               '--workers N to resize')
    if not yes:
        what = (f'apply {pool_yaml}' if task is not None
                else f'resize to {workers} workers')
        click.confirm(f'Pool {pool_name or (task and task.name)}: '
                      f'{what}. Proceed?', abort=True)
    out = _jobs_engine().pool_apply(task, pool_name=pool_name,
                                    workers=workers)
    click.echo(f'Pool {out["name"]}: {out["workers"]} workers '
               f'(version {out["version"]})')
    click.echo(f'Watch: sky-tpu jobs pool status {out["name"]}   '
               f'launch onto it: sky-tpu jobs launch --pool '
               f'{out["name"]} task.yaml')


@jobs_pool.command('status')
@click.argument('pool_names', nargs=-1)
def jobs_pool_status_cmd(pool_names: tuple) -> None:
    """Show pool(s) and their workers' job assignments."""
    snaps = _jobs_engine().pool_status(list(pool_names) or None)
    if not snaps:
        click.echo('No pools.')
        return
    for s in snaps:
        click.echo(f'{s["name"]}: {s["status"]}  '
                   f'ready {s["ready_replicas"]}/{s["target_workers"]}  '
                   f'idle {s["idle_workers"]}')
        fmt = '  {:<4} {:<24} {:<14} {:<10}'
        click.echo(fmt.format('ID', 'CLUSTER', 'STATUS', 'JOB'))
        for r in s['replicas']:
            click.echo(fmt.format(
                r['replica_id'], (r['cluster_name'] or '')[:24],
                r['status'],
                r['assigned_job'] if r['assigned_job'] else 'idle'))


@jobs_pool.command('down')
@click.argument('pool_name')
@click.option('--purge', is_flag=True, default=False,
              help='Force-clean a pool whose controller died.')
@click.option('--yes', '-y', is_flag=True, default=False)
def jobs_pool_down_cmd(pool_name: str, purge: bool, yes: bool) -> None:
    """Tear down a pool and all its workers."""
    if not yes:
        click.confirm(f'Tear down pool {pool_name} and all its workers?',
                      abort=True)
    _jobs_engine().pool_down(pool_name, purge=purge)
    click.echo(f'Pool {pool_name}: down.')


@jobs.command('queue')
def jobs_queue() -> None:
    """List managed jobs."""
    rows = _jobs_engine().queue()
    fmt = '{:<6} {:<18} {:<16} {:>4} {:<20}'
    click.echo(fmt.format('ID', 'NAME', 'STATUS', 'REC', 'CLUSTER'))
    for j in rows:
        click.echo(fmt.format(j['job_id'], (j['name'] or '')[:18],
                              j['status'], j['recovery_count'],
                              j['cluster_name'] or '-'))
        for t in j.get('tasks') or []:
            click.echo(fmt.format(
                f' ↳{t["task_id"]}', (t['name'] or '')[:18],
                t['status'], t['recovery_count'],
                t['cluster_name'] or '-'))


@jobs.command('cancel')
@click.argument('job_id', type=int)
@click.option('--yes', '-y', is_flag=True, default=False)
def jobs_cancel(job_id: int, yes: bool) -> None:
    """Cancel a managed job (tears its cluster down)."""
    if not yes:
        click.confirm(f'Cancel managed job {job_id}?', abort=True)
    _jobs_engine().cancel(job_id)
    click.echo(f'Cancellation requested for job {job_id}.')


@jobs.command('logs')
@click.argument('job_id', type=int)
@click.option('--follow/--no-follow', default=True)
@click.option('--controller', is_flag=True, default=False,
              help='Show the controller log instead of the job output.')
def jobs_logs(job_id: int, follow: bool, controller: bool) -> None:
    """Tail a managed job's output (or its controller's log)."""
    server_mode = bool(os.environ.get('SKY_TPU_API_SERVER'))
    if controller:
        if server_mode:
            raise click.ClickException(
                '--controller logs live on the API-server host; run there '
                'without SKY_TPU_API_SERVER set.')
        from skypilot_tpu import jobs as jobs_lib
        for chunk in jobs_lib.tail_controller_logs(job_id, follow=follow):
            sys.stdout.buffer.write(chunk)
            sys.stdout.buffer.flush()
        return
    if server_mode:
        # The server's DB owns managed jobs; resolve the cluster through
        # it and stream via the server's log proxy.
        from skypilot_tpu.client import sdk
        records = [j for j in sdk.jobs_queue() if j['job_id'] == job_id]
        if not records:
            raise click.ClickException(f'No managed job {job_id}.')
        record, tail = records[0], sdk.tail_logs
    else:
        from skypilot_tpu import core as core_lib
        from skypilot_tpu import jobs as jobs_lib
        record, tail = jobs_lib.get(job_id), core_lib.tail_logs
    cluster, cjid = record['cluster_name'], record['cluster_job_id']
    if not cluster or cjid < 0:
        raise click.ClickException(
            f'Job {job_id} has no cluster yet ({record["status"]}); try '
            f'--controller for the launch narration.')
    for chunk in tail(cluster, cjid, follow=follow):
        sys.stdout.buffer.write(chunk)
        sys.stdout.buffer.flush()


@cli.group()
def serve() -> None:
    """Serving: replicated, auto-scaled services behind a load balancer."""


def _serve_engine():
    """serve facade: direct engine or SDK (mirrors _engine())."""
    if os.environ.get('SKY_TPU_API_SERVER'):
        from skypilot_tpu.client import sdk

        class _SdkServe:
            up = staticmethod(
                lambda task, service_name=None: sdk.serve_up(
                    task, service_name))
            update = staticmethod(sdk.serve_update)
            down = staticmethod(lambda name: sdk.serve_down(name))
            status = staticmethod(sdk.serve_status)
            restart_replica = staticmethod(sdk.serve_restart_replica)
        return _SdkServe
    from skypilot_tpu import serve as serve_lib
    return serve_lib


@serve.command('up')
@click.argument('task_yaml')
@click.option('--service-name', '-n', default=None)
@click.option('--env', multiple=True, help='KEY=VALUE env override.')
@click.option('--yes', '-y', is_flag=True, default=False)
def serve_up(task_yaml: str, service_name: Optional[str], env: tuple,
             yes: bool) -> None:
    """Start a service from a YAML with a `service:` section."""
    task = _load_task(task_yaml, env)
    if not yes:
        click.confirm(
            f'Starting service {service_name or task.name or task_yaml} '
            f'({task.resources!r} per replica). Proceed?', abort=True)
    out = _serve_engine().up(task, service_name)
    if out.get('respawned'):
        click.echo(f'Re-attached a controller to existing service '
                   f'{out["name"]} (crash recovery).')
    if out.get('warning'):
        click.echo(f'WARNING: {out["warning"]}')
    click.echo(f'Service: {out["name"]}  endpoint: {out["endpoint"]}')
    click.echo(f'Watch replicas: sky-tpu serve status {out["name"]}')


@serve.command('update')
@click.argument('service_name')
@click.argument('task_yaml')
@click.option('--env', multiple=True)
def serve_update(service_name: str, task_yaml: str, env: tuple) -> None:
    """Roll a service to a new task version (zero-downtime)."""
    task = _load_task(task_yaml, env)
    version = _serve_engine().update(task, service_name)
    click.echo(f'Service {service_name} rolling to version {version}.')


@serve.command('down')
@click.argument('service_name')
@click.option('--yes', '-y', is_flag=True, default=False)
def serve_down(service_name: str, yes: bool) -> None:
    """Tear down a service and all its replicas."""
    if not yes:
        click.confirm(f'Tear down service {service_name}?', abort=True)
    _serve_engine().down(service_name)
    click.echo(f'Service {service_name} torn down.')


@serve.command('restart-replica')
@click.argument('service_name')
@click.argument('replica_id', type=int)
@click.option('--yes', '-y', is_flag=True, default=False)
def serve_restart_replica(service_name: str, replica_id: int,
                          yes: bool) -> None:
    """Replace one replica: terminate it; the autoscaler launches a
    substitute to hold the target count."""
    if not yes:
        click.confirm(f'Restart replica {replica_id} of '
                      f'{service_name}?', abort=True)
    _serve_engine().restart_replica(service_name, replica_id)
    click.echo(f'Replica {replica_id} flagged for replacement.')


@serve.command('status')
@click.argument('service_name', required=False)
def serve_status(service_name: Optional[str]) -> None:
    """Show services and their replicas."""
    snaps = _serve_engine().status(service_name)
    if not snaps:
        click.echo('No services.')
        return
    for s in snaps:
        click.echo(f'{s["name"]}: {s["status"]} v{s["version"]} '
                   f'endpoint={s["endpoint"]} policy={s["policy"]}')
        if s.get('degraded_reason'):
            # Stale-pid detection (docs/robustness.md "Crash safety"):
            # the controller process is dead — say how to recover.
            click.echo(f'  !! {s["degraded_reason"]}')
            # Open intents are a normal in-flight journal when the
            # controller lives (every launch holds one while
            # provisioning); they are only an ALARM when nothing is
            # left alive to finish them.
            if s.get('intents_open'):
                click.echo(f'  !! {s["intents_open"]} lifecycle '
                           f'intent(s) open — recovery owed to the '
                           f'respawned controller')
        fmt = '  {:<4} {:<22} {:<14} {:<4} {:<24}'
        click.echo(fmt.format('ID', 'CLUSTER', 'STATUS', 'VER', 'URL'))
        for r in s['replicas']:
            click.echo(fmt.format(r['replica_id'], r['cluster_name'],
                                  r['status'], r['version'],
                                  r['url'] or '-'))
            # Integrity quarantine (docs/robustness.md "Data
            # integrity"): say WHY and for how long — the reason
            # column survives the drain-and-replace transitions.
            if r.get('quarantine_reason'):
                age = ''
                if r.get('quarantined_at'):
                    age = (f', {time.time() - r["quarantined_at"]:.0f}s'
                           f' ago')
                click.echo(f'       !! quarantined: '
                           f'{r["quarantine_reason"]}{age}')


@cli.group()
def api() -> None:
    """Manage the local API server."""


@api.command('start')
@click.option('--host', default='127.0.0.1')
@click.option('--port', type=int, default=common.DEFAULT_API_PORT)
@click.option('--foreground', is_flag=True, default=False)
def api_start(host: str, port: int, foreground: bool) -> None:
    """Start the API server (background daemon by default)."""
    import subprocess
    import time as time_lib

    from skypilot_tpu.utils import common as common_lib
    if foreground:
        from skypilot_tpu.server import app as server_app
        sys.argv = ['app', '--host', host, '--port', str(port)]
        server_app.main()
        return
    log = open(os.path.join(common_lib.base_dir(), 'api_server.log'), 'ab')
    subprocess.Popen(
        [sys.executable, '-m', 'skypilot_tpu.server.app',
         '--host', host, '--port', str(port)],
        stdout=log, stderr=subprocess.STDOUT, start_new_session=True)
    url = f'http://{host}:{port}'
    deadline = time_lib.time() + 15
    import requests as requests_lib
    while time_lib.time() < deadline:
        try:
            if requests_lib.get(f'{url}/api/health', timeout=1).ok:
                click.echo(f'API server running at {url}')
                click.echo(f'Point clients at it: '
                           f'export SKY_TPU_API_SERVER={url}')
                return
        except requests_lib.RequestException:
            time_lib.sleep(0.3)
    raise click.ClickException('API server failed to start (see '
                               '~/.sky_tpu/api_server.log)')


@api.command('stop')
def api_stop() -> None:
    """Stop the background API server."""
    import json as json_lib
    import signal

    from skypilot_tpu.utils import common as common_lib
    meta_path = os.path.join(common_lib.base_dir(), 'api_server.json')
    if not os.path.exists(meta_path):
        click.echo('No API server metadata found.')
        return
    with open(meta_path, encoding='utf-8') as f:
        meta = json_lib.load(f)
    try:
        os.kill(meta['pid'], signal.SIGTERM)
        click.echo(f'Stopped API server (pid {meta["pid"]}).')
    except ProcessLookupError:
        click.echo('API server not running.')
    os.unlink(meta_path)


@api.command('status')
def api_status() -> None:
    """Probe the API server's health."""
    from skypilot_tpu.client import sdk
    health = sdk.api_health()
    click.echo(f'{sdk.server_url()}: {health["status"]} '
               f'(v{health["version"]}, api {health["api_version"]})')


@api.command('login')
@click.option('--timeout', type=float, default=300.0,
              help='Seconds to wait for the browser authorization.')
def api_login(timeout: float) -> None:
    """Log in to a remote API server (PKCE browser flow).

    Opens the server's /auth/authorize page; once the (SSO-
    authenticated) browser confirms, the CLI receives a bearer token
    and persists it for subsequent commands.
    """
    import secrets as pysecrets
    import time as time_lib
    import webbrowser

    import requests as requests_lib

    from skypilot_tpu.client import sdk
    from skypilot_tpu.server.auth import sessions
    url = sdk.server_url()
    verifier = pysecrets.token_urlsafe(32)
    challenge = sessions.compute_code_challenge(verifier)
    authorize = f'{url}/auth/authorize?code_challenge={challenge}'
    click.echo(f'Authorize this CLI in your browser:\n  {authorize}')
    click.echo(f'Verification code: {sessions.user_code(challenge)} '
               '— the browser page must show the SAME code before you '
               'click Authorize.')
    try:
        webbrowser.open(authorize)
    except Exception:  # noqa: BLE001 — headless host; URL printed above
        pass
    deadline = time_lib.time() + timeout
    while time_lib.time() < deadline:
        try:
            r = requests_lib.post(f'{url}/auth/token',
                                  json={'code_verifier': verifier},
                                  timeout=10)
        except requests_lib.RequestException as e:
            raise click.ClickException(f'API server unreachable: {e}')
        if r.status_code == 200:
            token = r.json()['token']
            token_path = os.path.join(
                os.path.expanduser('~/.sky_tpu'), 'token')
            os.makedirs(os.path.dirname(token_path), exist_ok=True)
            fd = os.open(token_path, os.O_WRONLY | os.O_CREAT |
                         os.O_TRUNC, 0o600)
            with os.fdopen(fd, 'w') as f:
                f.write(token)
            click.echo(f'Logged in. Token saved to {token_path}; '
                       f'export SKY_TPU_API_TOKEN=$(cat {token_path})')
            return
        time_lib.sleep(2.0)
    raise click.ClickException('Login timed out (browser authorization '
                               'never arrived).')


def _remote() -> bool:
    """True when ops should go through the API server (its RBAC applies;
    acting on the local DB would mint tokens the server rejects)."""
    return bool(os.environ.get('SKY_TPU_API_SERVER'))


@cli.command('dump')
@click.option('--output', '-o', default=None)
@click.option('--no-logs', is_flag=True, default=False)
def dump(output, no_logs) -> None:
    """Bundle state + logs into a diagnostics tarball (server-side
    state when an API server is configured, then downloaded)."""
    if _remote():
        from skypilot_tpu.client import sdk
        sdk.ensure_server_compatibility()
        remote_path = sdk.call('debug_dump',
                               {'include_logs': not no_logs})
        filename = os.path.basename(remote_path)
        local = output or filename
        sdk.download_dump(filename, local)
        click.echo(local)
        return
    from skypilot_tpu import core as core_lib
    path = core_lib.debug_dump(output, include_logs=not no_logs)
    click.echo(path)


@cli.group()
def users() -> None:
    """User management + service-account tokens (RBAC)."""


@users.command('ls')
def users_ls() -> None:
    if _remote():
        from skypilot_tpu.client import sdk
        rows = sdk.call('users.list')
    else:
        from skypilot_tpu import users as users_lib
        users_lib.core.ensure_user()
        rows = users_lib.list_users()
    fmt = '{:<10} {:<16} {:<8}'
    click.echo(fmt.format('ID', 'NAME', 'ROLE'))
    for u in rows:
        click.echo(fmt.format(u['id'], u['name'], u['role']))


@users.command('role')
@click.argument('user_id')
@click.argument('role')
def users_role(user_id: str, role: str) -> None:
    if _remote():
        from skypilot_tpu.client import sdk
        sdk.call('users.role', {'user_id': user_id, 'role': role})
    else:
        from skypilot_tpu import users as users_lib
        users_lib.update_role(user_id, role)
    click.echo(f'{user_id}: role={role}')


@users.command('token-create')
@click.argument('name')
@click.option('--expires-days', type=float, default=None)
def users_token_create(name: str, expires_days: Optional[float]) -> None:
    """Mint a service-account token (shown once; store it safely)."""
    expires = expires_days * 86400 if expires_days else None
    if _remote():
        from skypilot_tpu.client import sdk
        token = sdk.call('users.token_create',
                         {'name': name, 'expires_in_s': expires})
    else:
        from skypilot_tpu import users as users_lib
        token = users_lib.create_token(name, expires_in_s=expires)
    click.echo(token)


@users.command('tokens')
def users_tokens() -> None:
    if _remote():
        from skypilot_tpu.client import sdk
        rows = sdk.call('users.token_list')
    else:
        from skypilot_tpu import users as users_lib
        rows = users_lib.list_tokens()
    fmt = '{:<18} {:<14} {:<10} {:<8}'
    click.echo(fmt.format('TOKEN_ID', 'NAME', 'USER', 'REVOKED'))
    for t in rows:
        click.echo(fmt.format(t['token_id'], t['name'], t['user_id'],
                              'yes' if t['revoked'] else 'no'))


@users.command('token-revoke')
@click.argument('token_id')
def users_token_revoke(token_id: str) -> None:
    if _remote():
        from skypilot_tpu.client import sdk
        sdk.call('users.token_revoke', {'token_id': token_id})
    else:
        from skypilot_tpu import users as users_lib
        users_lib.revoke_token(token_id)
    click.echo(f'{token_id}: revoked')


@cli.group()
def workspaces() -> None:
    """Workspaces: scoped cluster/config namespaces."""


@workspaces.command('ls')
def workspaces_ls() -> None:
    from skypilot_tpu import workspaces as ws_lib
    if _remote():
        from skypilot_tpu.client import sdk
        all_ws = sdk.call('workspaces.list')
    else:
        all_ws = ws_lib.get_workspaces()
    active = ws_lib.active_workspace()
    for name, cfg in all_ws.items():
        mark = '*' if name == active else ' '
        priv = ' (private)' if (cfg or {}).get('private') else ''
        click.echo(f'{mark} {name}{priv}')


@workspaces.command('create')
@click.argument('name')
@click.option('--private', is_flag=True, default=False)
@click.option('--allowed-user', 'allowed_users', multiple=True)
def workspaces_create(name: str, private: bool,
                      allowed_users: tuple) -> None:
    cfg = {}
    if private:
        cfg['private'] = True
        cfg['allowed_users'] = list(allowed_users)
    if _remote():
        from skypilot_tpu.client import sdk
        sdk.call('workspaces.create', {'name': name, 'config': cfg})
    else:
        from skypilot_tpu import workspaces as ws_lib
        ws_lib.create_workspace(name, cfg)
    click.echo(f'Workspace {name} created.')


@workspaces.command('delete')
@click.argument('name')
def workspaces_delete(name: str) -> None:
    if _remote():
        from skypilot_tpu.client import sdk
        sdk.call('workspaces.delete', {'name': name})
    else:
        from skypilot_tpu import workspaces as ws_lib
        ws_lib.delete_workspace(name)
    click.echo(f'Workspace {name} deleted.')


@workspaces.command('switch')
@click.argument('name')
def workspaces_switch(name: str) -> None:
    """Set the active workspace in the global config."""
    from skypilot_tpu import config as config_lib
    from skypilot_tpu import users as users_lib
    from skypilot_tpu import workspaces as ws_lib
    # Raises for unknown workspaces and for private ones that exclude
    # the local identity.
    ws_lib.check_workspace_permission(users_lib.core.ensure_user(), name)
    config_lib.update_global({'active_workspace': name})
    click.echo(f'Active workspace: {name}')


@cli.group()
def pools() -> None:
    """Bare-metal SSH node pools (reference `sky ssh`)."""


@pools.command('ls')
def pools_ls() -> None:
    if _remote():
        from skypilot_tpu.client import sdk
        all_pools = sdk.call('pools.list')
    else:
        from skypilot_tpu.ssh_node_pools import SSHNodePoolManager
        all_pools = SSHNodePoolManager().get_all_pools()
    fmt = '{:<16} {:<7} {:<14} {:<6} {}'
    click.echo(fmt.format('POOL', 'HOSTS', 'ACCELERATOR', 'MODE',
                          'FIRST_HOST'))
    for name, cfg in all_pools.items():
        click.echo(fmt.format(name, len(cfg['hosts']),
                              cfg.get('accelerator', '-'),
                              cfg.get('mode', 'ssh'), cfg['hosts'][0]))


@pools.command('apply')
@click.argument('spec_yaml')
def pools_apply(spec_yaml: str) -> None:
    """Add/update pools from a YAML mapping of pool-name -> config.

    Pools live on the API server when one is configured — launches
    resolve pools server-side.
    """
    import yaml as yaml_lib
    with open(os.path.expanduser(spec_yaml), encoding='utf-8') as f:
        cfg = yaml_lib.safe_load(f) or {}
    if _remote():
        from skypilot_tpu.client import sdk
        sdk.call('pools.apply', {'pools': cfg})
    else:
        from skypilot_tpu.ssh_node_pools import SSHNodePoolManager
        SSHNodePoolManager().update_pools(cfg)
    click.echo(f'Pools updated: {", ".join(cfg)}')


@pools.command('delete')
@click.argument('name')
def pools_delete(name: str) -> None:
    if _remote():
        from skypilot_tpu.client import sdk
        ok = sdk.call('pools.delete', {'name': name})
    else:
        from skypilot_tpu.ssh_node_pools import SSHNodePoolManager
        ok = SSHNodePoolManager().delete_pool(name)
    if ok:
        click.echo(f'Pool {name} deleted.')
    else:
        raise click.ClickException(f'No such pool: {name}')


@cli.group()
def volumes() -> None:
    """Persistent volumes (gcp-pd, gcsfuse, hostpath)."""


@volumes.command('apply')
@click.argument('spec_yaml')
def volumes_apply(spec_yaml: str) -> None:
    """Create/register a volume from a YAML spec."""
    import yaml as yaml_lib
    with open(os.path.expanduser(spec_yaml), encoding='utf-8') as f:
        cfg = yaml_lib.safe_load(f) or {}
    if _remote():
        from skypilot_tpu.client import sdk
        rec = sdk.call('volumes.apply', {'spec': cfg})
    else:
        from skypilot_tpu import volumes as volumes_lib
        rec = volumes_lib.volume_apply(cfg)
    click.echo(f'Volume {rec["name"]} ({rec["type"]}): {rec["status"]}')


@volumes.command('ls')
def volumes_ls() -> None:
    if _remote():
        from skypilot_tpu.client import sdk
        rows = sdk.call('volumes.list')
    else:
        from skypilot_tpu import volumes as volumes_lib
        rows = volumes_lib.volume_list()
    fmt = '{:<16} {:<10} {:<8} {:<14} {:>8} {:<10} {:<16}'
    click.echo(fmt.format('NAME', 'TYPE', 'CLOUD', 'ZONE', 'SIZE_GB',
                          'STATUS', 'ATTACHED_TO'))
    for v in rows:
        click.echo(fmt.format(v['name'], v['type'], v['cloud'],
                              v['zone'] or '-', v['size_gb'] or '-',
                              v['status'], v['attached_to'] or '-'))


@volumes.command('delete')
@click.argument('names', nargs=-1, required=True)
@click.option('--yes', '-y', is_flag=True, default=False)
def volumes_delete(names: tuple, yes: bool) -> None:
    if not yes:
        click.confirm(f'Delete volume(s) {", ".join(names)}?', abort=True)
    if _remote():
        from skypilot_tpu.client import sdk
        sdk.call('volumes.delete', {'names': list(names)})
    else:
        from skypilot_tpu import volumes as volumes_lib
        volumes_lib.volume_delete(list(names))
    click.echo('Deleted.')


@cli.group()
def recipe() -> None:
    """Recipe hub: shareable, validated task templates
    (reference sky/recipes)."""


@recipe.command('add')
@click.argument('name')
@click.argument('task_yaml')
@click.option('--description', '-d', default='')
def recipe_add(name: str, task_yaml: str, description: str) -> None:
    """Validate + store TASK_YAML as recipe NAME."""
    with open(task_yaml, encoding='utf-8') as f:
        yaml_str = f.read()
    if _remote():
        from skypilot_tpu.client import sdk
        sdk.call('recipes.add', {'name': name, 'yaml': yaml_str,
                                 'description': description})
    else:
        from skypilot_tpu import recipes as recipes_lib
        recipes_lib.add(name, yaml_str, description=description)
    click.echo(f'Recipe {name!r} saved.')


@recipe.command('ls')
def recipe_ls() -> None:
    if _remote():
        from skypilot_tpu.client import sdk
        rows = sdk.call('recipes.list')
    else:
        from skypilot_tpu import recipes as recipes_lib
        rows = recipes_lib.list_recipes()
    fmt = '{:<24} {:<4} {:<16} {}'
    click.echo(fmt.format('NAME', 'VER', 'BY', 'DESCRIPTION'))
    for r in rows:
        click.echo(fmt.format(r['name'], 'v' + str(r['version']),
                              (r.get('created_by') or '-')[:15],
                              r.get('description') or '-'))


@recipe.command('show')
@click.argument('name')
def recipe_show(name: str) -> None:
    """Print a recipe's YAML."""
    if _remote():
        from skypilot_tpu.client import sdk
        rec = sdk.call('recipes.get', {'name': name})
    else:
        from skypilot_tpu import recipes as recipes_lib
        rec = recipes_lib.get(name)
    click.echo(rec['yaml'])


@recipe.command('rm')
@click.argument('name')
@click.option('--yes', '-y', is_flag=True, default=False)
def recipe_rm(name: str, yes: bool) -> None:
    if not yes:
        click.confirm(f'Delete recipe {name}?', abort=True)
    if _remote():
        from skypilot_tpu.client import sdk
        sdk.call('recipes.delete', {'name': name})
    else:
        from skypilot_tpu import recipes as recipes_lib
        recipes_lib.delete(name)
    click.echo(f'Recipe {name!r} deleted.')


@recipe.command('launch')
@click.argument('name')
@click.option('--cluster', '-c', default=None)
@click.option('--env', multiple=True, help='KEY=VALUE env override.')
@click.option('--yes', '-y', is_flag=True, default=False)
def recipe_launch(name: str, cluster: Optional[str], env: tuple,
                  yes: bool) -> None:
    """Launch a stored recipe (single-task recipes)."""
    bad = [e for e in env if '=' not in e]
    if bad:
        raise click.UsageError(
            f'--env must be KEY=VALUE, got {bad[0]!r}')
    envs = dict(e.split('=', 1) for e in env)
    if not yes:
        click.confirm(f'Launch recipe {name}?', abort=True)
    if _remote():
        from skypilot_tpu.client import sdk
        out = sdk.call('recipes.launch', {'name': name,
                                          'cluster_name': cluster,
                                          'env_overrides': envs})
        click.echo(f'Launched: {out}')
    else:
        from skypilot_tpu import recipes as recipes_lib
        job_id, info = recipes_lib.launch(name, cluster,
                                          env_overrides=envs)
        click.echo(f'Cluster: {info.cluster_name}  job: {job_id}')


def main() -> None:
    try:
        cli(standalone_mode=False)
    except click.Abort:
        click.echo('Aborted.')
        sys.exit(1)
    except click.ClickException as e:
        e.show()
        sys.exit(e.exit_code)
    except sky.exceptions.SkyTpuError as e:
        click.echo(f'Error: {e}', err=True)
        sys.exit(1)


if __name__ == '__main__':
    main()
