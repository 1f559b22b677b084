"""The load balancer's inbound leg: its handler entry to the server's."""
from benchmark.metrics import _phases


def read(run):
    return _phases.mean_ms(run, 'submit.lb_recv_t', 'submit.recv_t')
