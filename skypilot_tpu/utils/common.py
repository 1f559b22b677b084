"""Shared paths, enums, and small helpers."""
from __future__ import annotations

import enum
import os
import time
import uuid

HOME_ENV_VAR = 'SKY_TPU_HOME'
DEFAULT_API_PORT = 46580
# Per-request wall-clock budget in seconds, propagated serve LB →
# infer server → engine (docs/robustness.md "Zero-downtime serving"):
# the LB forwards the REMAINING budget on every retry/resume leg, the
# server turns it into an absolute deadline, and the engine cancels
# queued or decoding requests past it. Lives here (not in serve/ or
# infer/) so the LB never has to import the jax-heavy infer stack.
DEADLINE_HEADER = 'X-SkyTpu-Deadline-S'
# Multi-tenant identity on /generate, propagated serve LB → infer
# server → engine scheduler (docs/serving.md "Engine scheduler"): the
# unit of weighted fair queueing, per-tenant admission quotas, and the
# per-tenant metric breakdown. Absent header = the 'default' tenant.
# Same placement rationale as DEADLINE_HEADER.
TENANT_HEADER = 'X-SkyTpu-Tenant'
# Disaggregated prefill/decode (docs/serving.md): when the serve LB's
# fleet prefix index knows another replica holds a longer cached prefix
# of this prompt than the selected replica, it names that donor's URL
# here; the receiving server pulls the cached KV pages from the donor
# (/kv/export) before prefilling, so only the boundary is recomputed.
# Best-effort end to end — any pull failure degrades to plain
# recompute, never a client-visible error.
KV_DONOR_HEADER = 'X-SkyTpu-KV-Donor'
# Wall-clock moment (epoch seconds) the serve LB's handler received
# the request, forwarded on every leg: the infer server hands it to
# the engine's flight recorder, where it starts the request's
# timeline (docs/observability.md "Flight recorder"). Observed only;
# LB-internal like the donor header, so a client's value is dropped.
LB_RECV_HEADER = 'X-SkyTpu-LB-Recv-T'


# Directories base_dir() has already created this process: the call
# sits on hot DB paths (every serve-state query resolves the root),
# and an unconditional os.makedirs per call is measurable at fleet
# scale (~1µs*4 syscalls x millions of state reads in the twin).
_made_dirs: set = set()


def base_dir() -> str:
    """Framework state root (~/.sky_tpu, overridable for tests)."""
    d = os.path.expanduser(os.environ.get(HOME_ENV_VAR, '~/.sky_tpu'))
    # isdir-guarded memo: one cheap stat instead of four makedirs
    # syscalls on the hot path, but a root deleted mid-process (test
    # cleanup, operator rm -rf) is still recreated — direct writers
    # like api_server.json depend on it.
    if d not in _made_dirs or not os.path.isdir(d):
        os.makedirs(d, exist_ok=True)
        _made_dirs.add(d)
    return d


def logs_dir() -> str:
    d = os.path.join(base_dir(), 'logs')
    os.makedirs(d, exist_ok=True)
    return d


def clusters_dir() -> str:
    d = os.path.join(base_dir(), 'clusters')
    os.makedirs(d, exist_ok=True)
    return d


class ClusterStatus(enum.Enum):
    """Lifecycle of a cluster (reference sky/utils/status_lib.py semantics)."""
    INIT = 'INIT'          # provisioning in progress or unknown
    UP = 'UP'              # all hosts running, runtime healthy
    STOPPED = 'STOPPED'    # hosts stopped, disk kept


class JobStatus(enum.Enum):
    """Per-cluster job queue states (reference sky/skylet/job_lib.py:156)."""
    INIT = 'INIT'
    PENDING = 'PENDING'
    SETTING_UP = 'SETTING_UP'
    RUNNING = 'RUNNING'
    SUCCEEDED = 'SUCCEEDED'
    FAILED = 'FAILED'
    FAILED_SETUP = 'FAILED_SETUP'
    CANCELLED = 'CANCELLED'

    def is_terminal(self) -> bool:
        return self in (JobStatus.SUCCEEDED, JobStatus.FAILED,
                        JobStatus.FAILED_SETUP, JobStatus.CANCELLED)


def new_request_id() -> str:
    return uuid.uuid4().hex[:16]


def now() -> float:
    return time.time()


def readable_time_duration(seconds: float) -> str:
    seconds = int(seconds)
    if seconds < 60:
        return f'{seconds}s'
    if seconds < 3600:
        return f'{seconds // 60}m {seconds % 60}s'
    return f'{seconds // 3600}h {(seconds % 3600) // 60}m'


def free_port() -> int:
    """An ephemeral port that was free at probe time."""
    import socket
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        return s.getsockname()[1]


def pid_alive(pid: int) -> bool:
    if not pid or pid <= 0:
        return False
    try:
        os.kill(pid, 0)
    except (ProcessLookupError, PermissionError):
        return False
    # A zombie still answers kill(pid, 0) but is dead. This matters for
    # crash detection (docs/robustness.md "Crash safety"): a kill -9'd
    # detached controller is orphaned onto pid 1, and in containers
    # whose init does not reap, the corpse lingers as Z forever — it
    # must read as crashed, or `serve status` reports a dead control
    # plane healthy and `serve down` waits on it. The comm field in
    # /proc/<pid>/stat may contain spaces/parens; the state letter is
    # the first field after the LAST ')'.
    try:
        with open(f'/proc/{pid}/stat', encoding='ascii',
                  errors='replace') as f:
            stat = f.read()
        return stat.rsplit(')', 1)[1].split()[0] != 'Z'
    except (OSError, IndexError):
        return True   # no procfs (macOS): keep the kill(0) verdict
