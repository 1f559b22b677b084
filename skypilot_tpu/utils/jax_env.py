"""What a compute process runs on, and where it keeps compiled programs.

Every process that owns an accelerator (``infer.server``, ``train.run``,
``__graft_entry__``, the children of ``chip_smoke.py``)
answers both questions through this module, so a result can always say
which device produced it and two processes of one checkout always share
one persistent XLA compilation cache.

Nothing here imports jax at module import: the control plane imports
``skypilot_tpu.utils`` freely and must stay off the accelerator.
"""
from __future__ import annotations

import os
from typing import Any, Dict, List, Optional

CACHE_ENV = 'JAX_COMPILATION_CACHE_DIR'
# The cache key includes the directory, so the default never moves: one
# fixed, git-ignored path beside the package (the checkout root).
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), '.jax_compile_cache')


def compile_cache_dir(flag_dir: Optional[str] = None) -> str:
    """The directory this process must cache compiles in: the
    environment's if it names one (whoever placed the machine's cache
    wins over any flag), else ``flag_dir``, else the checkout's fixed
    default."""
    return os.environ.get(CACHE_ENV) or flag_dir or DEFAULT_CACHE_DIR


def attach_compile_cache(flag_dir: Optional[str] = None) -> str:
    """Attach jax's persistent compilation cache at
    :func:`compile_cache_dir` and return the directory jax reports in
    force. With ``JAX_COMPILATION_CACHE_DIR`` set jax has already read
    it, and no other directory is set in code. Thresholds are "cache
    everything": the default min-compile-time gate would skip exactly
    the small warm-path programs a restart replays. Raises on failure;
    callers that must boot regardless catch it themselves."""
    import jax
    path = compile_cache_dir(flag_dir)
    os.makedirs(path, exist_ok=True)
    if not os.environ.get(CACHE_ENV):
        jax.config.update('jax_compilation_cache_dir', path)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
    return jax.config.jax_compilation_cache_dir


def device_summary() -> Dict[str, Any]:
    """``{'platform', 'device_kind', 'count'}`` as jax reports them.
    Initialises the backend: call it only from the process that does
    the work, and carry its answer with that work's results."""
    import jax
    devices = jax.devices()
    return {'platform': devices[0].platform,
            'device_kind': devices[0].device_kind,
            'count': len(devices)}


def device_memory() -> List[Optional[int]]:
    """``bytes_in_use`` of every local device, in ``jax.local_devices()``
    order; None where the backend keeps no such statistic (CPU)."""
    import jax
    return [(d.memory_stats() or {}).get('bytes_in_use')
            for d in jax.local_devices()]
