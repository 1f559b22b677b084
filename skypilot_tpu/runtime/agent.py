"""On-host agent daemon — the skylet equivalent.

Counterpart of the reference's ``sky/skylet/skylet.py`` (gRPC server for
autostop/jobs services + periodic event loop, :45-85). Differences:

- HTTP/JSON over aiohttp instead of gRPC+protobuf (fastapi/protoc stubs are
  not part of this environment; the wire format is a private detail behind
  ``AgentClient``).
- **No Ray.** Gang execution is native: the agent knows its slice's host
  list and fans a job out to every host simultaneously with
  `jax.distributed` env injected per rank
  (``runtime/distributed_env.py``) — replacing the reference's generated
  Ray placement-group driver program (reference
  sky/backends/task_codegen.py:439-465,559).

Modes:
- ``local-slice``: one agent simulates all N hosts of a fake slice by
  spawning N local subprocesses per job (the test/E2E backend).
- ``host``: one agent per real TPU host; the head host's agent fans out to
  peer agents' /run_rank endpoint over the slice's internal network.

Run: ``python -m skypilot_tpu.runtime.agent --cluster-dir DIR``
(config read from DIR/agent_config.json; chosen port written to
DIR/agent.json).
"""
from __future__ import annotations

import argparse
import asyncio
import hmac
import json
import os
import signal
import sys
import time
from typing import Any, Dict, List, Optional

from aiohttp import web

from skypilot_tpu import topology
from skypilot_tpu.observability import trace as trace_lib
from skypilot_tpu.runtime import distributed_env
from skypilot_tpu.runtime import job_lib
from skypilot_tpu.utils import common
from skypilot_tpu.utils import failpoints

POLL_INTERVAL = 1.0
AUTOSTOP_CHECK_INTERVAL = 5.0


class Agent:
    def __init__(self, cluster_dir: str):
        # A provision-time trace context inherited from the spawning
        # provisioner must not become the parent of every span this
        # long-lived daemon ever records — context arrives per request
        # (traceparent header) or per job (SKY_TPU_TRACEPARENT in the
        # job's envs), never from the daemon's own environment.
        os.environ.pop(trace_lib.CTX_ENV_VAR, None)
        trace_lib.set_hop('agent')
        self.cluster_dir = os.path.abspath(cluster_dir)
        with open(os.path.join(self.cluster_dir, 'agent_config.json'),
                  encoding='utf-8') as f:
            self.config: Dict[str, Any] = json.load(f)
        # Tracing config rides agent_config.json for real (remote)
        # hosts, where the provisioner's environment does not reach:
        # `trace_enabled` turns span recording on, `trace_collector`
        # names the URL spans ship to (the API server as seen FROM the
        # cluster). On the local fake slice the inherited env already
        # carries both.
        if self.config.get('trace_enabled'):
            os.environ.setdefault(trace_lib.ENV_VAR, '1')
        if self.config.get('trace_collector'):
            os.environ.setdefault(trace_lib.COLLECTOR_ENV_VAR,
                                  str(self.config['trace_collector']))
        self.mode: str = self.config.get('mode', 'local-slice')
        self.host_rank: int = int(self.config.get('host_rank', 0))
        self.host_ips: List[str] = self.config.get('host_ips', ['127.0.0.1'])
        self.peer_agent_urls: List[str] = self.config.get(
            'peer_agent_urls', [])
        slice_name = self.config.get('tpu_slice')
        self.tpu_slice: Optional[topology.TpuSlice] = (
            topology.parse_tpu(slice_name) if slice_name else None)
        self.num_hosts: int = int(self.config.get(
            'num_hosts', self.tpu_slice.num_hosts if self.tpu_slice else 1))
        # Multislice (DCN): num_hosts is per slice; this host's slice is
        # config['slice_id'] (host mode); local-slice mode simulates all
        # num_slices * num_hosts ranks in one process tree.
        self.num_slices: int = int(self.config.get('num_slices', 1))
        self.slice_id: int = int(self.config.get('slice_id', 0))
        self.jobs = job_lib.JobTable(
            os.path.join(self.cluster_dir, 'jobs.db'))
        self.started_at = time.time()
        # Per-cluster shared secret, provision-time generated. The agent
        # binds a routable interface on real clouds, so every endpoint
        # except /health requires it (the reference never exposes skylet
        # at all — gRPC rides an SSH tunnel,
        # cloud_vm_ray_backend.py:2288-2320; a bearer token over the VPC
        # is this framework's equivalent trust boundary).
        self._token_cache = (-1.0, self.config.get('auth_token'))
        # Cluster TLS (utils/tls.py): cert+key PEMs ride agent_config
        # next to the bearer token; all agents of a cluster share one
        # cert, so peer fan-out pins the same fingerprint it serves.
        self.tls_cert_pem: Optional[str] = self.config.get('tls_cert_pem')
        self.tls_key_pem: Optional[str] = self.config.get('tls_key_pem')
        self.cert_fingerprint: Optional[str] = None
        if self.tls_cert_pem:
            from skypilot_tpu.utils import tls
            self.cert_fingerprint = tls.fingerprint_of_pem(
                self.tls_cert_pem)
        # autostop state (reference sky/skylet/autostop_lib.py)
        self._autostop_file = os.path.join(self.cluster_dir, 'autostop.json')
        # job_id -> list of subprocess handles (local-slice mode)
        self._procs: Dict[int, List[asyncio.subprocess.Process]] = {}
        # /exec invocations get unique negative ids so their proc/pgid
        # bookkeeping is cleaned per call (a shared -1 key would
        # accumulate handles forever on exec-heavy clusters).
        self._exec_counter = 0
        self._cancelled: set = set()
        # submit_id -> job_id dedup map for idempotent /submit retries
        # (insertion-ordered; oldest entries evicted past the cap).
        self._submit_ids: Dict[str, int] = {}
        # Restart reconciliation: a previous agent killed mid-job (stop,
        # OOM, crash) leaves INIT/SETTING_UP/RUNNING rows behind with no
        # process behind them. The FIFO scheduler gates on
        # running_jobs(), so an unreconciled row would wedge the queue
        # FOREVER (every later submit stays PENDING). This process just
        # started: no job of ours can be running yet — mark the
        # orphans FAILED (the managed-jobs controller treats a terminal
        # status on a healthy slice per its restart policy; a preempted
        # slice never restarts an agent, so the preemption-detection
        # path in _kill_agent is unaffected).
        for stale in self.jobs.running_jobs():
            self.jobs.set_status(stale['job_id'], job_lib.JobStatus.FAILED)
        # Native orphan reaper (native/reaper.cc): if this agent is
        # SIGKILLed mid-job, the rank process groups recorded in the
        # pgid file are torn down so no leaked rank wedges the TPU chip
        # (reference subprocess_daemon.py:184, rebuilt native).
        self._pgid_file = os.path.join(self.cluster_dir, 'job_pgids')
        open(self._pgid_file, 'w', encoding='utf-8').close()
        self._start_reaper()

    def _auth_token(self) -> Optional[str]:
        """Live cluster token: re-read agent_config.json when it changes
        so a re-provision can rotate the secret without an agent
        restart (providers rewrite the config on every run_instances)."""
        path = os.path.join(self.cluster_dir, 'agent_config.json')
        try:
            mtime = os.stat(path).st_mtime
        except OSError:
            return self._token_cache[1]
        if mtime != self._token_cache[0]:
            try:
                with open(path, encoding='utf-8') as f:
                    tok = json.load(f).get('auth_token')
                self._token_cache = (mtime, tok)
            except (OSError, json.JSONDecodeError):
                pass   # mid-rewrite read; keep the cached token
        return self._token_cache[1]

    def _auth_headers(self) -> Dict[str, str]:
        tok = self._auth_token()
        return {'Authorization': f'Bearer {tok}'} if tok else {}

    def _start_reaper(self) -> None:
        import subprocess as sp

        from skypilot_tpu.runtime import native_build
        reaper = native_build.ensure_reaper()
        if reaper is None:
            return
        sp.Popen([reaper, '--parent-pid', str(os.getpid()),
                  '--pgid-file', self._pgid_file],
                 stdout=sp.DEVNULL, stderr=sp.DEVNULL,
                 start_new_session=True)

    def _record_pgid(self, pid: int) -> None:
        try:
            with open(self._pgid_file, 'a', encoding='utf-8') as f:
                f.write(f'{pid}\n')
        except OSError:
            pass

    def _prune_pgids(self, pids) -> None:
        """Drop finished ranks' pgids from the reaper file — but ONLY
        groups that are really gone: a rank leader can exit while a
        backgrounded child keeps the group alive, and that survivor
        must stay covered by the reaper/teardown (it could be holding
        libtpu). Entries only ever accumulated before, which was the
        opposite hazard: teardown acting on pids the OS had recycled."""
        gone = set()
        for p in pids:
            try:
                os.killpg(int(p), 0)
            except ProcessLookupError:
                gone.add(str(p))
            except PermissionError:
                pass   # group alive (not ours to probe): keep covered
        if not gone:
            return
        try:
            with open(self._pgid_file, encoding='utf-8') as f:
                live = [ln for ln in f.read().split()
                        if ln and ln not in gone]
            tmp = self._pgid_file + '.tmp'
            with open(tmp, 'w', encoding='utf-8') as f:
                f.write(''.join(f'{ln}\n' for ln in live))
            os.replace(tmp, self._pgid_file)
        except OSError:
            pass

    # ---------------- job execution --------------------------------------
    def _rank_env(self, rank: int, job_envs: Dict[str, str],
                  job_id: int) -> Dict[str, str]:
        """Env for global host index `rank` (slice-aware).

        `rank` spans all slices; slice j owns ranks
        [j*num_hosts, (j+1)*num_hosts). make_env gets the slice-local view
        (libtpu TPU_WORKER_* is per slice) plus the global coordinator.
        """
        env = dict(os.environ)
        sid, in_rank = divmod(rank, self.num_hosts)
        slice_ips = self.host_ips[sid * self.num_hosts:
                                  (sid + 1) * self.num_hosts]
        env.update(distributed_env.make_env(
            slice_ips, in_rank, self.tpu_slice,
            num_slices=self.num_slices, slice_id=sid,
            megascale_coordinator=(self.host_ips[0]
                                   if self.num_slices > 1 else None),
            coordinator_ip=self.host_ips[0]))
        env.update(job_envs)
        env['SKY_TPU_JOB_ID'] = str(job_id)
        if self.mode == 'local-slice':
            # Fake-slice sandbox root: absolute file-mount destinations land
            # under this dir (a real host would use / directly).
            env['SKY_TPU_HOST_ROOT'] = os.path.join(self.cluster_dir,
                                                    f'host{rank}')
            # Rank cwd is the host workdir, so first-party modules (e.g.
            # `python -m skypilot_tpu.infer.server` replicas) are only
            # importable if the framework root rides PYTHONPATH — the
            # local analog of the wheel a real host has installed.
            pkg_root = os.path.dirname(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))))
            prior_pp = env.get('PYTHONPATH', '')
            if pkg_root not in prior_pp.split(os.pathsep):
                env['PYTHONPATH'] = (f'{pkg_root}{os.pathsep}{prior_pp}'
                                     if prior_pp else pkg_root)
            # Fake slices must not grab a real TPU. Overridden (not
            # setdefault): the inherited environment may pin a TPU platform.
            env['JAX_PLATFORMS'] = 'cpu'
            if self.tpu_slice is not None:
                flag = ('--xla_force_host_platform_device_count='
                        f'{self.tpu_slice.chips_per_host}')
                prior = env.get('XLA_FLAGS', '')
                if '--xla_force_host_platform_device_count' not in prior:
                    env['XLA_FLAGS'] = f'{prior} {flag}'.strip()
        return env

    def _rank_cwd(self, rank: int) -> str:
        if self.mode == 'local-slice':
            d = os.path.join(self.cluster_dir, f'host{rank}', 'workdir')
        else:
            d = os.path.join(self.cluster_dir, 'workdir')
        os.makedirs(d, exist_ok=True)
        return d

    async def _run_rank(self, job_id: int, rank: int, cmd: str,
                        envs: Dict[str, str], log_path: str) -> int:
        os.makedirs(os.path.dirname(log_path), exist_ok=True)
        with open(log_path, 'ab') as logf:
            proc = await asyncio.create_subprocess_shell(
                cmd,
                cwd=self._rank_cwd(rank),
                env=self._rank_env(rank, envs, job_id),
                stdout=logf,
                stderr=asyncio.subprocess.STDOUT,
                start_new_session=True,
            )
        self._procs.setdefault(job_id, []).append(proc)
        # start_new_session=True → the child's pgid is its pid.
        self._record_pgid(proc.pid)
        return await proc.wait()

    async def _run_job(self, job: Dict[str, Any]) -> None:
        job_id = job['job_id']
        log_dir = job['log_dir']
        os.makedirs(log_dir, exist_ok=True)
        # Re-adopt the submitting request's trace context (persisted in
        # the job envs by h_submit) — the job-runtime hop of the trace.
        trace_ctx = trace_lib.context_from(
            (job['envs'] or {}).get(trace_lib.CTX_ENV_VAR))
        try:
            with trace_ctx:
                if job['setup_cmd']:
                    self.jobs.set_status(job_id,
                                         job_lib.JobStatus.SETTING_UP)
                    with trace_lib.span('job.setup', job_id=job_id):
                        rcs = await self._fan_out(job_id,
                                                  job['setup_cmd'],
                                                  job['envs'], log_dir,
                                                  'setup')
                    if any(rc != 0 for rc in rcs):
                        self.jobs.set_status(
                            job_id, job_lib.JobStatus.FAILED_SETUP)
                        return
                self.jobs.set_status(job_id, job_lib.JobStatus.RUNNING)
                with trace_lib.span('job.run', job_id=job_id,
                                    hosts=self.num_hosts *
                                    self.num_slices) as jspan:
                    rcs = await self._fan_out(job_id, job['run_cmd'],
                                              job['envs'], log_dir, 'run')
                    if jspan is not None:
                        jspan.set_attr('returncodes', rcs)
            if job_id in self._cancelled:
                self.jobs.set_status(job_id, job_lib.JobStatus.CANCELLED)
            elif all(rc == 0 for rc in rcs):
                self.jobs.set_status(job_id, job_lib.JobStatus.SUCCEEDED)
            else:
                self.jobs.set_status(job_id, job_lib.JobStatus.FAILED)
        except Exception as e:  # noqa: BLE001 — agent must not die on a job
            with open(os.path.join(log_dir, 'agent_error.log'), 'a',
                      encoding='utf-8') as f:
                f.write(f'{e!r}\n')
            self.jobs.set_status(job_id, job_lib.JobStatus.FAILED)
        finally:
            procs = self._procs.pop(job_id, None) or []
            self._prune_pgids(p.pid for p in procs)
            if trace_lib.enabled():
                await asyncio.get_event_loop().run_in_executor(
                    None, trace_lib.flush)

    async def _fan_out(self, job_id: int, cmd: str, envs: Dict[str, str],
                       log_dir: str, phase: str) -> List[int]:
        """Run `cmd` on every host of the slice simultaneously."""
        if self.mode == 'local-slice':
            tasks = [
                self._run_rank(job_id, r, cmd, envs,
                               os.path.join(log_dir, f'rank{r}_{phase}.log'))
                for r in range(self.num_hosts * self.num_slices)
            ]
            return list(await asyncio.gather(*tasks))
        # host mode: this agent runs its own rank; peers run theirs.
        import aiohttp
        my = self._run_rank(job_id, self.host_rank, cmd, envs,
                            os.path.join(log_dir,
                                         f'rank{self.host_rank}_{phase}.log'))

        from skypilot_tpu.utils import tls
        peer_ssl = tls.aiohttp_ssl(self.cert_fingerprint)

        async def call_peer(sess: 'aiohttp.ClientSession', url: str) -> int:
            # Response body must be read while the session is open. The
            # cluster token rides the fan-out too — peers enforce it.
            async with sess.post(f'{url}/run_rank', json={
                    'job_id': job_id, 'cmd': cmd, 'envs': envs,
                    'phase': phase,
            }, headers=self._auth_headers(), ssl=peer_ssl,
                    timeout=aiohttp.ClientTimeout(total=None)) as res:
                body = await res.json()
                return int(body.get('returncode', 255))

        async with aiohttp.ClientSession() as sess:
            results = await asyncio.gather(
                my, *(call_peer(sess, url) for url in self.peer_agent_urls),
                return_exceptions=True)
        return [255 if isinstance(r, BaseException) else int(r)
                for r in results]

    async def scheduler_loop(self) -> None:
        """FIFO, one job at a time (reference JobSchedulerEvent,
        sky/skylet/events.py:69)."""
        while True:
            try:
                if not self.jobs.running_jobs():
                    nxt = self.jobs.next_pending()
                    if nxt is not None:
                        self.jobs.set_status(nxt['job_id'],
                                             job_lib.JobStatus.INIT)
                        asyncio.get_event_loop().create_task(
                            self._run_job(nxt))
            except Exception:  # noqa: BLE001
                pass
            await asyncio.sleep(POLL_INTERVAL)

    # ---------------- autostop -------------------------------------------
    def _autostop_config(self) -> Dict[str, Any]:
        if os.path.exists(self._autostop_file):
            with open(self._autostop_file, encoding='utf-8') as f:
                return json.load(f)
        return {'idle_minutes': -1, 'down': False}

    async def heartbeat_loop(self) -> None:
        """Reference UsageHeartbeatReportEvent (sky/skylet/events.py:153):
        the on-cluster runtime reports liveness into the usage stream."""
        from skypilot_tpu import usage
        while True:
            try:
                usage.record('agent-heartbeat', 0.0, 'ok', {
                    'cluster': self.config.get('cluster_name', '?'),
                    'mode': self.mode,
                    'num_hosts': self.num_hosts,
                    'num_slices': self.num_slices,
                    'idle': self.jobs.is_idle(),
                })
            except Exception:  # noqa: BLE001 — telemetry is best-effort
                pass
            await asyncio.sleep(600.0)

    # ---------------- log GC ----------------------------------------------
    def _gc_logs(self, now: Optional[float] = None) -> None:
        """Prune finished jobs' logs by age AND total size (reference
        sky/jobs/log_gc.py: 7-day retention, hourly loop; the size
        budget is the TPU-host twist — a long-lived slice writes
        per-rank logs forever and eventually fills the host disk).

        Never touches a non-terminal job's logs; exec logs (setup /
        pre-exec stages) age out the same way. Tunables ride
        agent_config.json: log_retention_hours (negative disables),
        log_budget_mb (total across finished-job + exec logs).
        """
        import shutil
        now = now if now is not None else time.time()
        retention_h = float(self.config.get('log_retention_hours', 168))
        budget_bytes = float(self.config.get('log_budget_mb',
                                             1024)) * 1e6
        if retention_h < 0:
            return
        job_root = os.path.join(self.cluster_dir, 'job_logs')
        exec_root = os.path.join(self.cluster_dir, 'exec_logs')
        # Candidate dirs: terminal jobs' log dirs + all exec log dirs.
        candidates = []   # (mtime, size, path)
        terminal_ids = {
            str(j['job_id']) for j in self.jobs.list_jobs()
            if j['status'].is_terminal()}
        known_ids = {str(j['job_id']) for j in self.jobs.list_jobs()}
        if os.path.isdir(job_root):
            for name in os.listdir(job_root):
                # Unknown dirs (job row gone) are prunable; live jobs
                # are not.
                if name in known_ids and name not in terminal_ids:
                    continue
                candidates.append(os.path.join(job_root, name))
        if os.path.isdir(exec_root):
            candidates.extend(os.path.join(exec_root, name)
                              for name in os.listdir(exec_root))
        entries = []
        for path in candidates:
            try:
                mtime = os.path.getmtime(path)
                size = sum(
                    os.path.getsize(os.path.join(r, f))
                    for r, _, fs in os.walk(path) for f in fs)
            except OSError:
                continue
            entries.append((mtime, size, path))
        # Age pass.
        kept = []
        for mtime, size, path in sorted(entries):
            if now - mtime > retention_h * 3600:
                shutil.rmtree(path, ignore_errors=True)
            else:
                kept.append((mtime, size, path))
        # Size pass: oldest finished logs go first until under budget.
        total = sum(size for _, size, _ in kept)
        for mtime, size, path in kept:
            if total <= budget_bytes:
                break
            shutil.rmtree(path, ignore_errors=True)
            total -= size

    async def log_gc_loop(self) -> None:
        """Hourly (clamped like the reference's _next_gc_interval)."""
        retention_h = float(self.config.get('log_retention_hours', 168))
        interval = max(min(retention_h * 3600, 3600.0), 30.0)
        while True:
            try:
                self._gc_logs()
            except Exception:  # noqa: BLE001 — GC must not kill agent
                pass
            await asyncio.sleep(interval)

    async def autostop_loop(self) -> None:
        """Reference AutostopEvent (sky/skylet/events.py:161): the cluster
        tears *itself* down after idling."""
        while True:
            await asyncio.sleep(AUTOSTOP_CHECK_INTERVAL)
            try:
                cfg = self._autostop_config()
                idle_min = cfg.get('idle_minutes', -1)
                if idle_min is None or idle_min < 0:
                    continue
                if not self.jobs.is_idle():
                    continue
                anchor = max(self.jobs.last_activity(), self.started_at,
                             cfg.get('set_at', 0.0))
                if time.time() - anchor >= idle_min * 60:
                    self._trigger_autostop(bool(cfg.get('down', False)))
            except Exception:  # noqa: BLE001
                pass

    def _trigger_autostop(self, down: bool) -> None:
        marker = {
            'triggered_at': time.time(),
            'action': 'down' if down else 'stop',
        }
        with open(os.path.join(self.cluster_dir, 'autostop_triggered.json'),
                  'w', encoding='utf-8') as f:
            json.dump(marker, f)
        if self.mode == 'host':
            # Real cloud: the agent deletes/stops its own slice via the
            # provider API (reference autostop_lib self-teardown).
            try:
                from skypilot_tpu.provision.gcp import instance as gcp
                pc = self.config.get('provider_config', {})
                if down:
                    gcp.terminate_instances(self.config['cluster_name'], pc)
                else:
                    gcp.stop_instances(self.config['cluster_name'], pc)
            except Exception:  # noqa: BLE001
                pass
        else:
            # Local fake slice: mark hosts stopped; the engine's status
            # refresh reconciles.
            for r in range(self.num_hosts * self.num_slices):
                hd = os.path.join(self.cluster_dir, f'host{r}')
                if os.path.isdir(hd):
                    with open(os.path.join(hd, 'state'), 'w',
                              encoding='utf-8') as f:
                        f.write('STOPPED' if not down else 'TERMINATED')

    # ---------------- HTTP handlers --------------------------------------
    async def h_health(self, _req: web.Request) -> web.Response:
        # FailpointError surfaces as aiohttp's 500 — from the client's
        # side, indistinguishable from a crashing agent (the point).
        await failpoints.hit_async('agent.health')
        return web.json_response({
            'status': 'healthy',
            'uptime_s': time.time() - self.started_at,
            'idle': self.jobs.is_idle(),
            'mode': self.mode,
            'num_hosts': self.num_hosts,
            'num_slices': self.num_slices,
        })

    async def h_submit(self, req: web.Request) -> web.Response:
        # BEFORE any state change: an injected submit failure must be
        # safely retryable (no half-created job row to double-run).
        await failpoints.hit_async('agent.submit')
        body = await req.json()
        # Idempotent retry: the client stamps each LOGICAL submit with a
        # fresh submit_id and reuses it across retries. If the previous
        # attempt's response was lost AFTER the job row committed, the
        # retry must return the same job instead of double-running the
        # workload. In-memory is enough: the dedup window is the
        # client's retry loop, and an agent restart within it also loses
        # the job row the duplicate would have shadowed.
        submit_id = body.get('submit_id')
        if submit_id:
            prior = self._submit_ids.get(str(submit_id))
            if prior is not None:
                return web.json_response({'job_id': prior})
        log_dir = os.path.join(self.cluster_dir, 'job_logs')
        envs = dict(body.get('envs', {}))
        # Job execution is async (the scheduler loop picks it up later):
        # persist the submit's trace context in the job's envs so the
        # runtime spans (job.setup/job.run) — and the rank processes,
        # which inherit the env — parent to this submission.
        trace_lib.child_env(envs)
        job_id = self.jobs.add_job(
            name=body.get('name', 'job'),
            run_cmd=body['run'],
            setup_cmd=body.get('setup'),
            envs=envs,
            num_hosts=self.num_hosts * self.num_slices,
            log_dir='')
        log_dir = os.path.join(log_dir, str(job_id))
        self.jobs._conn.execute(  # set final log dir now that id is known
            'UPDATE jobs SET log_dir=? WHERE job_id=?', (log_dir, job_id))
        self.jobs._conn.commit()
        if submit_id:
            self._submit_ids[str(submit_id)] = job_id
            if len(self._submit_ids) > 4096:   # bound the dedup window
                self._submit_ids.pop(next(iter(self._submit_ids)))
        return web.json_response({'job_id': job_id})

    async def h_jobs(self, _req: web.Request) -> web.Response:
        out = []
        for j in self.jobs.list_jobs():
            j = dict(j)
            j['status'] = j['status'].value
            out.append(j)
        return web.json_response({'jobs': out})

    async def h_job(self, req: web.Request) -> web.Response:
        job = self.jobs.get(int(req.match_info['job_id']))
        if job is None:
            return web.json_response({'error': 'not found'}, status=404)
        job = dict(job)
        job['status'] = job['status'].value
        return web.json_response(job)

    async def h_cancel(self, req: web.Request) -> web.Response:
        job_id = int(req.match_info['job_id'])
        job = self.jobs.get(job_id)
        if job is None:
            return web.json_response({'error': 'not found'}, status=404)
        self._cancelled.add(job_id)
        for proc in self._procs.get(job_id, []):
            try:
                os.killpg(os.getpgid(proc.pid), signal.SIGTERM)
            except (ProcessLookupError, PermissionError):
                pass
        if job['status'] in (job_lib.JobStatus.PENDING,):
            self.jobs.set_status(job_id, job_lib.JobStatus.CANCELLED)
        return web.json_response({'cancelled': job_id})

    async def h_logs(self, req: web.Request) -> web.StreamResponse:
        """Stream rank logs; ?follow=1 tails until the job ends
        (reference sky/skylet/log_lib.py tailing)."""
        await failpoints.hit_async('agent.tail')
        job_id = int(req.match_info['job_id'])
        job = self.jobs.get(job_id)
        if job is None:
            return web.json_response({'error': 'not found'}, status=404)
        follow = req.query.get('follow', '0') == '1'
        rank = int(req.query.get('rank', 0))
        resp = web.StreamResponse()
        resp.content_type = 'text/plain'
        await resp.prepare(req)
        log_dir = job['log_dir']
        setup_path = os.path.join(log_dir, f'rank{rank}_setup.log')
        run_path = os.path.join(log_dir, f'rank{rank}_run.log')
        # Stream both files concurrently by position: the setup phase only
        # writes the setup log, the run phase only the run log, so a single
        # interleaved pass moves from one to the other as the job advances
        # (a pure per-file loop would sit on the setup log until the job
        # *ends* and never show live run output).
        pos = {setup_path: 0, run_path: 0}

        async def drain(path: str) -> None:
            if not os.path.exists(path):
                return
            with open(path, 'rb') as f:
                f.seek(pos[path])
                chunk = f.read()
            if chunk:
                pos[path] += len(chunk)
                await resp.write(chunk)

        while True:
            job = self.jobs.get(job_id)
            await drain(setup_path)
            await drain(run_path)
            if not follow or job['status'].is_terminal():
                # Final drain catches writes between read and status check.
                await drain(setup_path)
                await drain(run_path)
                break
            await asyncio.sleep(0.2)
        await resp.write_eof()
        return resp

    async def h_exec(self, req: web.Request) -> web.Response:
        """Synchronous command on all hosts (setup / pre-exec stages)."""
        body = await req.json()
        self._exec_counter += 1
        exec_id = -self._exec_counter
        log_dir = os.path.join(self.cluster_dir, 'exec_logs',
                               str(int(time.time() * 1000)))
        try:
            rcs = await self._fan_out(exec_id, body['cmd'],
                                      body.get('envs', {}),
                                      log_dir, 'exec')
        finally:
            procs = self._procs.pop(exec_id, None) or []
            self._prune_pgids(p.pid for p in procs)
        tails = {}
        for r in range(len(rcs)):
            p = os.path.join(log_dir, f'rank{r}_exec.log')
            if os.path.exists(p):
                with open(p, encoding='utf-8', errors='replace') as f:
                    tails[r] = f.read()[-2000:]
        return web.json_response({'returncodes': rcs, 'tails': tails})

    async def h_run_rank(self, req: web.Request) -> web.Response:
        """Peer-host execution endpoint (host mode fan-out target)."""
        body = await req.json()
        log_dir = os.path.join(self.cluster_dir, 'job_logs',
                               str(body['job_id']))
        job_id = int(body['job_id'])
        rc = await self._run_rank(
            job_id, self.host_rank, body['cmd'],
            body.get('envs', {}),
            os.path.join(log_dir,
                         f'rank{self.host_rank}_{body["phase"]}.log'))
        # Peers have no _run_job finally: clean this call's handle and
        # reaper entry here or they accumulate for the agent's lifetime.
        procs = self._procs.get(job_id, [])
        done = [p for p in procs if p.returncode is not None]
        for p in done:
            procs.remove(p)
        if not procs:
            self._procs.pop(job_id, None)
        self._prune_pgids(p.pid for p in done)
        return web.json_response({'returncode': rc})

    async def h_autostop(self, req: web.Request) -> web.Response:
        if req.method == 'POST':
            body = await req.json()
            body['set_at'] = time.time()
            with open(self._autostop_file, 'w', encoding='utf-8') as f:
                json.dump(body, f)
            return web.json_response({'ok': True})
        return web.json_response(self._autostop_config())

    def make_app(self) -> web.Application:
        @web.middleware
        async def _trace(request: web.Request, handler):
            # Mutating endpoints get an agent-hop span parented to the
            # caller's traceparent header. GET/stream endpoints (log
            # tails can live for a job's whole runtime) stay untraced.
            if not trace_lib.enabled() or request.method != 'POST':
                return await handler(request)
            # Span names use the ROUTE TEMPLATE ('/cancel/{job_id}'),
            # not the raw path — per-id names would mint a metric label
            # per job and exhaust the server's label-cardinality cap.
            try:
                name = request.match_info.route.resource.canonical
            except AttributeError:
                name = request.path
            with trace_lib.context_from(
                    request.headers.get(trace_lib.HEADER)), \
                    trace_lib.span(f'agent.{name}'):
                resp = await handler(request)
            # Ship promptly (local store or the API server's collector);
            # off-loop: flush may do file/HTTP IO.
            await asyncio.get_event_loop().run_in_executor(
                None, trace_lib.flush)
            return resp

        @web.middleware
        async def _auth(request: web.Request, handler):
            if request.path == '/health':
                return await handler(request)
            token = self._auth_token()
            if not token:
                # Secure by default: an agent provisioned without a
                # token serves liveness only. Every provider generates
                # one; hitting this means a hand-rolled config.
                return web.json_response(
                    {'error': 'agent has no auth token configured; '
                              'only /health is served'}, status=403)
            hdr = request.headers.get('Authorization', '')
            presented = hdr[len('Bearer '):] if \
                hdr.startswith('Bearer ') else ''
            if not hmac.compare_digest(presented, token):
                return web.json_response({'error': 'forbidden'},
                                         status=403)
            return await handler(request)

        app = web.Application(middlewares=[_auth, _trace])
        app.router.add_get('/health', self.h_health)
        app.router.add_post('/submit', self.h_submit)
        app.router.add_get('/jobs', self.h_jobs)
        app.router.add_get('/jobs/{job_id}', self.h_job)
        app.router.add_post('/cancel/{job_id}', self.h_cancel)
        app.router.add_get('/logs/{job_id}', self.h_logs)
        app.router.add_post('/exec', self.h_exec)
        app.router.add_post('/run_rank', self.h_run_rank)
        app.router.add_route('*', '/autostop', self.h_autostop)
        return app


async def _main(cluster_dir: str, host: str, port: int) -> None:
    agent = Agent(cluster_dir)
    app = agent.make_app()
    runner = web.AppRunner(app)
    await runner.setup()
    ssl_ctx = None
    if agent.tls_cert_pem and agent.tls_key_pem:
        from skypilot_tpu.utils import tls
        ssl_ctx = tls.server_context(agent.tls_cert_pem,
                                     agent.tls_key_pem,
                                     workdir=agent.cluster_dir)
    site = web.TCPSite(runner, host, port, ssl_context=ssl_ctx)
    await site.start()
    actual_port = site._server.sockets[0].getsockname()[1]  # noqa: SLF001
    scheme = 'https' if ssl_ctx is not None else 'http'
    # Atomic publish: provisioners poll for this file and JSON-parse it the
    # moment it appears, so a plain open/write races with the reader.
    agent_json = os.path.join(cluster_dir, 'agent.json')
    tmp = agent_json + '.tmp'
    with open(tmp, 'w', encoding='utf-8') as f:
        json.dump({'url': f'{scheme}://{host}:{actual_port}',
                   'pid': os.getpid(),
                   'cert_fingerprint': agent.cert_fingerprint}, f)
    os.replace(tmp, agent_json)
    loop = asyncio.get_event_loop()
    loop.create_task(agent.scheduler_loop())
    loop.create_task(agent.autostop_loop())
    loop.create_task(agent.heartbeat_loop())
    loop.create_task(agent.log_gc_loop())
    while True:
        await asyncio.sleep(3600)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument('--cluster-dir', required=True)
    parser.add_argument('--host', default='127.0.0.1')
    parser.add_argument('--port', type=int, default=0)
    args = parser.parse_args()
    try:
        asyncio.run(_main(args.cluster_dir, args.host, args.port))
    except KeyboardInterrupt:
        sys.exit(0)


if __name__ == '__main__':
    main()
