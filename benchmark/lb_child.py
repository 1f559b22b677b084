"""The serve load balancer as a CPU-only child of a benchmark run.

What the serve controller does for a replica that passed its readiness
probe (and what ``chip_smoke.py::_child_lb`` does): register the replica
READY in ``serve/state`` under this run's ``SKY_TPU_HOME`` and run the
real load balancer in front of it. The parent sets ``JAX_PLATFORMS=cpu``:
this process must never reach for the chip.

``python benchmark/lb_child.py <service> <lb-port> <replica-url>``
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    service, port, replica_url = sys.argv[1], int(sys.argv[2]), sys.argv[3]
    from skypilot_tpu.serve import load_balancer
    from skypilot_tpu.serve import state as serve_state
    serve_state.add_service(service, spec_json='{}', task_yaml='',
                            lb_port=port, lb_policy='least_load')
    rid = serve_state.add_replica(service, 'benchmark', 1)
    serve_state.set_replica_url(rid, replica_url)
    serve_state.set_replica_status(rid, serve_state.ReplicaStatus.READY)
    load_balancer.run_load_balancer(service, 'least_load', '127.0.0.1', port)


if __name__ == '__main__':
    main()
