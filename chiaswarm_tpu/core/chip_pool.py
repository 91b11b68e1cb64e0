"""ChipPool — TPU-native replacement for the reference's GPU device layer.

The reference wraps each CUDA GPU in a ``Device`` object with a non-blocking
mutex, seed injection, and a ``"cuda:N"`` device string passed to every
workload callback (swarm/gpu/device.py:6-47). On TPU the executor is not one
chip but a *mesh slot*: the pool partitions the addressable chips into one or
more submeshes (job-level data parallelism across slots, SPMD parallelism
within a slot) and wraps each in an :class:`MeshSlot` that preserves the
reference's contract:

- non-blocking busy check (busy slot -> ``SlotBusy``),
- ``model_name`` popped from kwargs and passed positionally,
- a seed drawn when the job does not pin one, recorded into the result
  config for reproducibility (parity with swarm/gpu/device.py:36-43).

Workload callbacks keep the uniform signature of the reference
(swarm/generator.py -> swarm/job_arguments.py seam)::

    callback(slot, model_name, **kwargs) -> (artifacts dict, pipeline config)

but receive a :class:`MeshSlot` (mesh + rng + precision) instead of a device
string.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Sequence

import jax
from jax.sharding import Mesh

from chiaswarm_tpu.core.mesh import MeshSpec, build_mesh
from chiaswarm_tpu.core.rng import draw_seed, key_for_seed


class SlotBusy(RuntimeError):
    """Raised when a job is dispatched to a slot that is already executing
    at full pipeline depth (parity with the reference's non-blocking
    mutex, swarm/gpu/device.py:27-29 — generalized to a bounded counter)."""


@dataclasses.dataclass
class MeshSlot:
    """One schedulable executor: a device mesh plus per-job RNG state.

    ``depth`` is the slot's job-pipeline depth: how many jobs may be
    in flight at once. The reference's torch Device is a hard mutex
    (depth 1) because its pipelines are stateful modules; these pipelines
    are pure jitted functions, so a second job can safely tokenize and
    dispatch its program while the first drains its device->host image
    transfer — XLA serializes execution on the chip's stream and the
    overlap removes the chip-idle gap. What the overlap is worth on this
    chip is not measured (no benchmark cell has two jobs in flight:
    PERF.md section 7, question 0). Depth 2 captures the overlap;
    deeper only grows queue latency.
    """

    index: int
    mesh: Mesh
    depth: int = 2

    def __post_init__(self) -> None:
        self._slots_free = threading.BoundedSemaphore(max(1, self.depth))

    @property
    def identifier(self) -> str:
        return f"tpu-slot:{self.index}"

    @property
    def data_width(self) -> int:
        """Size of the mesh's ``data`` axis (1 when absent) — how many
        batch rows execute in parallel; drives queue sizing and the
        cross-job coalescing burst size (node/worker.py)."""
        return int(dict(zip(self.mesh.axis_names,
                            self.mesh.devices.shape)).get("data", 1))

    def descriptor(self) -> dict[str, Any]:
        devices = self.mesh.devices.flatten().tolist()
        dev0 = devices[0]
        return {
            "slot": self.index,
            "platform": dev0.platform,
            "device_kind": dev0.device_kind,
            "chips": len(devices),
            "mesh_shape": dict(zip(self.mesh.axis_names, self.mesh.devices.shape)),
        }

    def __call__(self, callback: Callable[..., tuple[dict, dict]], **kwargs):
        """Run ``callback`` on this slot, injecting seed + mesh.

        Mirrors Device.__call__ (swarm/gpu/device.py:26-47): non-blocking
        acquire, seed bookkeeping, model_name passed positionally.
        """
        if not self._slots_free.acquire(blocking=False):
            raise SlotBusy(f"{self.identifier} is busy")
        try:
            model_name = kwargs.pop("model_name", None)
            seed = kwargs.pop("seed", None)
            if seed is None:
                seed = draw_seed()
            seed = int(seed)
            artifacts, config = callback(
                self, model_name, seed=seed, **kwargs
            )
            config = dict(config)
            config["seed"] = seed
            return artifacts, config
        finally:
            self._slots_free.release()

    def call_multi(self, callback: Callable[..., list], **kwargs) -> list:
        """``__call__`` variant for coalesced callbacks that return a
        LIST of per-job (artifacts, config) — per-job seeds ride inside
        ``kwargs["jobs"]`` and each config already records its own seed
        (node/executor.py::synchronous_do_work_batch)."""
        if not self._slots_free.acquire(blocking=False):
            raise SlotBusy(f"{self.identifier} is busy")
        try:
            model_name = kwargs.pop("model_name", None)
            seed = int(kwargs.pop("seed", 0))
            outs = callback(self, model_name, seed=seed, **kwargs)
            return [(artifacts, dict(config)) for artifacts, config in outs]
        finally:
            self._slots_free.release()

    def rng(self, seed: int) -> jax.Array:
        return key_for_seed(seed)


class ChipPool:
    """Partition the addressable chips into ``n_slots`` mesh slots.

    ``n_slots=1`` (default) gives one pod-wide SPMD slot — the idiomatic TPU
    shape, where a whole batch of jobs is executed as one sharded program.
    ``n_slots=len(devices)`` reproduces the reference's one-job-per-device
    scheduling for latency-sensitive mixed workloads.
    """

    def __init__(
        self,
        n_slots: int = 1,
        mesh_spec: MeshSpec | None = None,
        devices: Sequence[jax.Device] | None = None,
        depth: int = 2,
    ) -> None:
        devices = list(devices) if devices is not None else list(jax.devices())
        if n_slots < 1 or len(devices) % n_slots:
            raise ValueError(
                f"cannot split {len(devices)} chips into {n_slots} slots"
            )
        per_slot = len(devices) // n_slots
        self.slots = [
            MeshSlot(
                index=i,
                mesh=build_mesh(mesh_spec, devices=devices[i * per_slot:(i + 1) * per_slot]),
                depth=depth,
            )
            for i in range(n_slots)
        ]

    def __len__(self) -> int:
        return len(self.slots)

    def __iter__(self):
        return iter(self.slots)

    def descriptor(self) -> list[dict[str, Any]]:
        return [slot.descriptor() for slot in self.slots]
