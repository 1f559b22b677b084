"""A benchmark run with the timed path broken underneath.

``python tests/benchmark/faulty_run.py <fault> <run.py arguments>``
plants the fault in the program, then drives ``benchmark/run.py`` as it
is: the harness must see ``correct`` come out false. Only the tests call
this; the harness has no option for it.

- ``alter_token``: the engine's sampler puts the second-best token where
  the best belongs, at the place where tokens are produced.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def plant(fault: str) -> None:
    if fault != 'alter_token':
        raise SystemExit(f'unknown fault {fault!r}')
    import jax.numpy as jnp

    from skypilot_tpu.infer import sampling
    honest = sampling.sample

    def second_best(logits, key, temps, top_k=0):
        best = jnp.argmax(logits, axis=-1, keepdims=True)
        masked = jnp.where(jnp.arange(logits.shape[-1]) == best,
                           -jnp.inf, logits)
        return honest(masked, key, temps, top_k=top_k)
    sampling.sample = second_best


if __name__ == '__main__':
    plant(sys.argv[1])
    from benchmark import run
    sys.exit(run.main(sys.argv[2:]))
