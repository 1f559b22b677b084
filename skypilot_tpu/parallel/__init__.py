"""Parallelism: device meshes, sharding rules, collectives.

The TPU-native replacement for everything the reference delegates to
torchrun/NCCL (SURVEY.md §2.8): DP/FSDP/TP via `jax.sharding` +
NamedSharding over a Mesh; SP via ring attention (`ops/ring_attention.py`);
XLA emits the collectives over ICI/DCN.
"""
