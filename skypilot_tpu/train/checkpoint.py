"""Orbax checkpointing: the managed-jobs checkpoint/resume convention.

The reference has no model checkpointing in-tree; its recovery pattern is
"mount a bucket, write checkpoints there, re-run resumes from the bucket"
(reference llm/llama-3_1-finetuning/lora.yaml:27-31; SURVEY.md §5). This
module is that pattern made concrete for JAX: async Orbax saves into a
directory (typically a gcsfuse-mounted bucket — ``data/storage.py``), and
``restore_or_init`` is what recovered jobs call on startup.
"""
from __future__ import annotations

import os
from typing import Any, Optional

import jax
import orbax.checkpoint as ocp

DEFAULT_CHECKPOINT_DIR_ENV = 'SKY_TPU_CHECKPOINT_DIR'


class CheckpointManager:
    """Thin wrapper over orbax CheckpointManager with async saves."""

    def __init__(self, directory: str, *, max_to_keep: int = 3,
                 save_interval_steps: int = 1):
        self.directory = os.path.abspath(os.path.expanduser(directory))
        os.makedirs(self.directory, exist_ok=True)
        self._mgr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep,
                save_interval_steps=save_interval_steps,
                enable_async_checkpointing=True,
            ))

    def save(self, step: int, state: Any, *, force: bool = False) -> bool:
        return self._mgr.save(step, args=ocp.args.StandardSave(state),
                              force=force)

    def restore(self, step: Optional[int] = None,
                target: Optional[Any] = None) -> Any:
        step = self.latest_step() if step is None else step
        if step is None:
            raise FileNotFoundError(
                f'No checkpoint under {self.directory}')
        if target is not None:
            target_struct = jax.tree_util.tree_map(
                ocp.utils.to_shape_dtype_struct, target)
            return self._mgr.restore(
                step, args=ocp.args.StandardRestore(target_struct))
        return self._mgr.restore(step)

    def restore_to_host(self, target: Any,
                        step: Optional[int] = None) -> Any:
        """Restore onto the HOST (cpu backend), not the accelerator.

        The int8 serving path needs this: an 8B bf16 checkpoint (16 GB)
        cannot first land on the 16 GB chip it is being quantized to
        fit — it restores into host RAM and quantizes leaf-by-leaf onto
        the device (ops/quant.py quantize_params_transfer). ``target``
        is a concrete or abstract pytree giving shapes/dtypes."""
        step = self.latest_step() if step is None else step
        if step is None:
            raise FileNotFoundError(
                f'No checkpoint under {self.directory}')
        try:
            cpu = jax.local_devices(backend='cpu')[0]
        except RuntimeError as e:
            # jax initialises only the platforms JAX_PLATFORMS lists:
            # 'tpu' alone leaves no cpu backend to restore into (unset,
            # or 'tpu,cpu' as on the chip machines, keeps it).
            raise RuntimeError(
                f'restore_to_host needs jax\'s cpu backend beside the '
                f'accelerator; JAX_PLATFORMS='
                f'{os.environ.get("JAX_PLATFORMS")!r} does not select '
                f'it (use e.g. "tpu,cpu")') from e
        sharding = jax.sharding.SingleDeviceSharding(cpu)
        target_struct = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                           sharding=sharding),
            jax.tree_util.tree_map(ocp.utils.to_shape_dtype_struct,
                                   target))
        return self._mgr.restore(
            step, args=ocp.args.StandardRestore(target_struct))

    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def wait(self) -> None:
        """Block until async saves are durable (call before teardown)."""
        self._mgr.wait_until_finished()

    def close(self) -> None:
        self._mgr.close()


def restore_or_init(directory: str, init_fn, *,
                    target: Optional[Any] = None) -> tuple:
    """The resume convention: restore the latest checkpoint if one exists,
    else initialize fresh. Returns (state, restored: bool)."""
    mgr = CheckpointManager(directory)
    step = mgr.latest_step()
    if step is None:
        return init_fn(), False
    state = mgr.restore(step, target=target if target is not None
                        else init_fn())
    return state, True
