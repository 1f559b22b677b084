"""Per-cluster file locks (reference sky/utils/locks.py).

The engine's planner-under-lock discipline (reference
sky/execution.py:469-487): every state-mutating operation on a cluster takes
its lock so concurrent launches/downs serialize.
"""
from __future__ import annotations

import contextlib
import os
from typing import Iterator

import filelock

from skypilot_tpu.utils import common


def _lock_path(name: str) -> str:
    d = os.path.join(common.base_dir(), 'locks')
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, f'{name}.lock')


@contextlib.contextmanager
def cluster_lock(cluster_name: str,
                 timeout: float = 60.0) -> Iterator[None]:
    lock = filelock.FileLock(_lock_path(f'cluster_{cluster_name}'),
                             timeout=timeout)
    with lock:
        yield


# One FileLock instance per path: distinct instances on the same path
# conflict even within a process (flock is per-open-file), so nested
# named_lock() calls (workspace CRUD -> config.update_global) would
# deadlock. A shared instance is reentrant and still serializes threads.
_named_locks: dict = {}
_named_locks_guard = __import__('threading').Lock()


@contextlib.contextmanager
def named_lock(name: str, timeout: float = 60.0) -> Iterator[None]:
    """General-purpose cross-process lock (config writes, etc.)."""
    path = _lock_path(name)
    with _named_locks_guard:
        lock = _named_locks.get(path)
        if lock is None:
            lock = filelock.FileLock(path, timeout=timeout)
            _named_locks[path] = lock
    with lock:
        yield
