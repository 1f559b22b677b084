"""`jax.distributed` / libtpu environment wiring for slice hosts.

This replaces the reference's rank/world env contract
(``SKYPILOT_NODE_RANK``/``SKYPILOT_NODE_IPS``/``SKYPILOT_NUM_NODES``,
reference sky/skylet/constants.py:469-474, consumed by torchrun in
examples/resnet_distributed_torch.yaml:31-34). The TPU equivalent wires the
XLA/libtpu process group instead of NCCL:

- ``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID``:
  read by ``infer/multihost.maybe_initialize_distributed`` and passed to
  ``jax.distributed.initialize()`` explicitly (jax itself reads only the
  coordinator address from the environment).
- ``TPU_WORKER_ID`` / ``TPU_WORKER_HOSTNAMES``: libtpu's own multi-host
  wiring (what the TPU VM metadata server would provide); exporting them
  makes the framework authoritative, which is required when running
  non-default topologies or fake local slices.
- ``MEGASCALE_*``: multislice (DCN-connected slices) coordinator variables,
  emitted only when a job spans multiple slices.

The generic ``SKY_TPU_*`` variables remain for user scripts that want
rank/ips without importing jax.
"""
from __future__ import annotations

from typing import Dict, List, Optional

from skypilot_tpu import topology

# Port the jax.distributed coordinator (host 0) listens on.
COORDINATOR_PORT = 8476
MEGASCALE_PORT = 8081

# Generic env (cloud-framework-agnostic), reference constants.py:469-474.
NODE_RANK_ENV = 'SKY_TPU_NODE_RANK'
NODE_IPS_ENV = 'SKY_TPU_NODE_IPS'
NUM_NODES_ENV = 'SKY_TPU_NUM_NODES'
NUM_CHIPS_PER_NODE_ENV = 'SKY_TPU_NUM_CHIPS_PER_NODE'


def make_env(host_ips: List[str],
             rank: int,
             tpu_slice: Optional[topology.TpuSlice],
             *,
             num_slices: int = 1,
             slice_id: int = 0,
             megascale_coordinator: Optional[str] = None,
             coordinator_ip: Optional[str] = None) -> Dict[str, str]:
    """Env vars for the process running on host `rank` of a slice.

    `host_ips` is THIS slice's host list and `rank` the host index within
    it (libtpu's TPU_WORKER_* wiring is per-slice). For multislice jobs
    (num_slices > 1) `slice_id` identifies the slice, `coordinator_ip`
    must be host 0 of slice 0 (the ONE jax.distributed coordinator for the
    global process group), and MEGASCALE vars carry the DCN-level wiring.
    """
    num_hosts = len(host_ips)
    coordinator = f'{coordinator_ip or host_ips[0]}:{COORDINATOR_PORT}'
    env = {
        NODE_RANK_ENV: str(rank),
        NODE_IPS_ENV: '\n'.join(host_ips),
        NUM_NODES_ENV: str(num_hosts),
        # multihost.maybe_initialize_distributed() passes these on.
        'JAX_COORDINATOR_ADDRESS': coordinator,
        'JAX_NUM_PROCESSES': str(num_hosts * num_slices),
        'JAX_PROCESS_ID': str(slice_id * num_hosts + rank),
    }
    if tpu_slice is not None:
        env[NUM_CHIPS_PER_NODE_ENV] = str(tpu_slice.chips_per_host)
        # libtpu multi-host wiring (authoritative topology).
        env['TPU_WORKER_ID'] = str(rank)
        env['TPU_WORKER_HOSTNAMES'] = ','.join(host_ips)
        env['TPU_CHIPS_PER_HOST_BOUNDS'] = _chips_per_host_bounds(tpu_slice)
        env['TPU_HOST_BOUNDS'] = ','.join(
            str(b) for b in tpu_slice.host_bounds())
        env['TPU_ACCELERATOR_TYPE'] = tpu_slice.accelerator_type
    if num_slices > 1:
        assert megascale_coordinator is not None
        env.update({
            'MEGASCALE_COORDINATOR_ADDRESS':
                f'{megascale_coordinator}:{MEGASCALE_PORT}',
            'MEGASCALE_NUM_SLICES': str(num_slices),
            'MEGASCALE_SLICE_ID': str(slice_id),
        })
    return env


def _chips_per_host_bounds(s: topology.TpuSlice) -> str:
    """The per-host chip block as 'x,y,z' (complement of host_bounds)."""
    hb = s.host_bounds()
    dims = [t // b for t, b in zip(s.ici_topology, hb)]
    while len(dims) < 3:
        dims.append(1)
    return ','.join(str(d) for d in dims)
