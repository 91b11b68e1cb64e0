"""Device mesh construction — the TPU-native replacement for the reference's
per-GPU device pool (swarm/gpu/device.py, swarm/gpu/device_pool.py).

Where the reference treats each CUDA GPU as an isolated executor, a TPU pod
is a single SPMD machine: we build a ``jax.sharding.Mesh`` over the chips and
express parallelism as named axes:

- ``"data"``  — batch / job-level data parallelism (ICI all-reduce free for
  inference; gradient psum for training)
- ``"model"`` — tensor parallelism (weight sharding for models larger than
  one chip's HBM, e.g. SDXL at high batch or cascade stages)
- ``"seq"``   — sequence/context parallelism (ring attention over ICI for
  long token counts: video, long-context transformers)

Multi-host pods use ``jax.distributed.initialize`` (DCN for the control
plane, ICI for collectives) — see chiaswarm_tpu.parallel.distributed.
"""

from __future__ import annotations

import dataclasses
import math
import os
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh

DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"

DEFAULT_AXES = (DATA_AXIS, MODEL_AXIS, SEQ_AXIS)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """A named request for a device mesh.

    ``shape`` maps axis name -> size. Sizes of ``-1`` mean "absorb all
    remaining devices" (at most one axis may be -1). Axes not listed get
    size 1. The product must equal (or, with a -1, divide) the device count.
    """

    shape: dict[str, int] = dataclasses.field(
        default_factory=lambda: {DATA_AXIS: -1}
    )
    axis_order: Sequence[str] = DEFAULT_AXES

    def resolve(self, n_devices: int) -> dict[str, int]:
        sizes = {axis: 1 for axis in self.axis_order}
        for axis, size in self.shape.items():
            if axis not in sizes:
                raise ValueError(f"unknown mesh axis {axis!r}; known: {list(sizes)}")
            sizes[axis] = size
        wildcard = [a for a, s in sizes.items() if s == -1]
        if len(wildcard) > 1:
            raise ValueError("at most one mesh axis may be -1")
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if wildcard:
            if n_devices % fixed:
                raise ValueError(
                    f"cannot factor {n_devices} devices into {sizes} "
                    f"(fixed product {fixed} does not divide)"
                )
            sizes[wildcard[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh {sizes} wants {fixed} devices but {n_devices} are present"
            )
        return sizes


def local_chip_count() -> int:
    return jax.local_device_count()


# stand-in budget for platforms WITHOUT device memory stats (the CPU test
# meshes); a TPU must report its own limit — see device_hbm_bytes
_DEFAULT_HBM_BYTES = 16 * 1024**3
# Two shares of one chip's HBM, for two different questions.
#
# _PARAM_HBM_FRACTION — the MESH policy's bar (derive_mesh_spec): a family
# whose params exceed it does not fit "comfortably" and is tensor-parallel
# sharded when the slot has the chips to do so.
#
# _RESIDENT_HBM_FRACTION — the RESIDENCY ledger's default budget
# (serving/residency.py): how much of a chip the whole resident set may
# hold; the rest is activations, compiled executables and lane state.
# The two were one number (0.35) until the first run of the normal path
# at SDXL width showed what that meant on a one-chip slot, where there is
# nothing to shard over: SDXL's 6.5 GiB of bf16 params exceeded 0.35 x
# 16 GiB, so the ledger degraded the headline model to load-per-job and
# no SDXL job could ever ride a lane. 0.6 is held to by chip_smoke.py,
# which fills the ledger to this budget before its waves: the width-4
# 1024 px SDXL lane and its decode must run above a FULL resident set,
# or the smoke fails (CHANGES.md PR 21 quotes the peak that run saw).
_PARAM_HBM_FRACTION = 0.35
_RESIDENT_HBM_FRACTION = 0.6

ENV_RESIDENCY_BUDGET = "CHIASWARM_RESIDENCY_BUDGET"


def _operator_budget_bytes() -> int | None:
    """``CHIASWARM_RESIDENCY_BUDGET`` (bytes): the operator's own figure
    for how much of a chip params may hold. It outranks BOTH fractions
    above — the ledger's budget and the mesh policy's bar move together
    under it, as they did when they were one number."""
    raw = os.environ.get(ENV_RESIDENCY_BUDGET, "").strip()
    try:
        return max(1, int(float(raw))) if raw else None
    except ValueError:
        return None  # malformed override: the fractions apply


def resident_param_budget_bytes(hbm_bytes: int | None = None) -> int:
    """Per-chip byte budget for RESIDENT model params — what the
    residency ledger (serving/residency.py) plans against until told
    otherwise: the operator's override, else ``_RESIDENT_HBM_FRACTION``
    of the chip's reported HBM."""
    override = _operator_budget_bytes()
    if override is not None:
        return override
    if hbm_bytes is None:
        hbm_bytes = device_hbm_bytes()
    return int(_RESIDENT_HBM_FRACTION * hbm_bytes)


def device_hbm_bytes(device: jax.Device | None = None) -> int:
    """Per-chip memory budget from the runtime. A TPU that reports no
    ``bytes_limit`` is an error (every budget downstream would be a
    guess about hardware we cannot see); only stat-less non-TPU
    platforms (CPU test meshes) get the stand-in constant."""
    device = device or jax.devices()[0]
    limit = int((device.memory_stats() or {}).get("bytes_limit", 0))
    if limit > 0:
        return limit
    if device.platform == "tpu":
        raise RuntimeError(
            f"{device} reports no memory_stats()['bytes_limit']; refusing "
            "to plan HBM budgets against a guessed chip size")
    return _DEFAULT_HBM_BYTES


def derive_mesh_spec(n_devices: int,
                     heaviest_param_bytes: int | None = None,
                     hbm_bytes: int | None = None,
                     latency: bool = False) -> MeshSpec:
    """Default dp x tp (x sp) policy for a serving pool — no hand-written
    ``mesh_shape`` required.

    Data parallelism is the throughput axis (cross-job coalescing rides
    it), so everything defaults to ``data``. Tensor parallelism engages
    ONLY when the heaviest catalog family's bf16 params would not fit
    comfortably on one chip (> _PARAM_HBM_FRACTION of HBM): tp doubles —
    over power-of-two divisors of the device count — until the per-chip
    shard fits. On a v5e-8 with SDXL in the catalog (~7 GB bf16) that
    lands on dp=4 x tp=2; SD1.5-only catalogs stay dp=8.

    ``latency=True`` (settings.latency_mode) flips the trade: the leftover
    devices go to the ``seq`` axis, so every job's large spatial
    self-attention runs as sequence-parallel ring attention over ICI
    (ops/attention.py::_try_ring) — shorter per-job latency instead of
    coalesced throughput."""
    if n_devices <= 1:
        return MeshSpec({DATA_AXIS: 1})
    budget = _operator_budget_bytes()
    if budget is None:
        if hbm_bytes is None:
            hbm_bytes = device_hbm_bytes()
        budget = _PARAM_HBM_FRACTION * hbm_bytes
    tp = 1
    if heaviest_param_bytes:
        while (heaviest_param_bytes / tp > budget
               and tp * 2 <= n_devices and n_devices % (tp * 2) == 0):
            tp *= 2
    rest = n_devices // tp
    # seq must divide the power-of-two spatial token counts (4096/1024/
    # 256/64) or _try_ring can never engage: cap it to the largest
    # power-of-two factor and return the remainder to data
    sp = rest & (-rest) if latency else 1
    if sp > 1:
        return MeshSpec({DATA_AXIS: rest // sp, MODEL_AXIS: tp,
                         SEQ_AXIS: sp})
    return MeshSpec({DATA_AXIS: rest, MODEL_AXIS: tp})


def build_mesh(
    spec: MeshSpec | None = None,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build a Mesh over ``devices`` (default: all addressable devices).

    Device order follows ``jax.devices()`` which already reflects ICI
    topology locality; the trailing (fastest-varying) mesh axis therefore
    rides the tightest ICI links — put the heaviest-communication axis
    (``seq`` for ring attention, else ``model``) last via ``axis_order``.
    """
    spec = spec or MeshSpec()
    devices = list(devices) if devices is not None else list(jax.devices())
    sizes = spec.resolve(len(devices))
    axis_names = tuple(spec.axis_order)
    shape = tuple(sizes[a] for a in axis_names)
    device_array = np.asarray(devices).reshape(shape)
    return Mesh(device_array, axis_names)


def split_mesh(mesh: Mesh, n: int = 2) -> list[Mesh]:
    """Partition ``mesh``'s devices into ``n`` contiguous data-axis
    submeshes — the substrate for stage-level pipeline parallelism
    (pipelines/cascade.py::generate_stage_parallel): each pipeline stage's
    params live on its own submesh, so XLA's async dispatch runs stage k
    of item i concurrently with stage k-1 of item i+1 on disjoint chips.

    Contiguous slices follow ``jax.devices()`` order, so each submesh
    keeps the tightest ICI locality available. Requires the device count
    to divide evenly."""
    devices = mesh.devices.flatten().tolist()
    if n < 1 or len(devices) % n:
        raise ValueError(
            f"cannot split {len(devices)} devices into {n} submeshes")
    per = len(devices) // n
    return [
        build_mesh(MeshSpec({DATA_AXIS: per}),
                   devices=devices[i * per:(i + 1) * per])
        for i in range(n)
    ]


def single_device_mesh(device: jax.Device | None = None) -> Mesh:
    """A 1x1x1 mesh for one chip — lets every pipeline be written against a
    mesh unconditionally (no separate single-chip code path)."""
    device = device or jax.devices()[0]
    return build_mesh(MeshSpec({DATA_AXIS: 1, MODEL_AXIS: 1, SEQ_AXIS: 1}),
                      devices=[device])


def host_cpu_mesh(n: int = 8) -> Mesh:
    """Testing helper: a CPU mesh (requires
    XLA_FLAGS=--xla_force_host_platform_device_count=N set before jax import,
    as done in tests/conftest.py)."""
    cpus = jax.devices("cpu")
    return build_mesh(MeshSpec({DATA_AXIS: -1}), devices=cpus[:n])


def env_forced_host_devices() -> int | None:
    flags = os.environ.get("XLA_FLAGS", "")
    for token in flags.split():
        if token.startswith("--xla_force_host_platform_device_count="):
            return int(token.split("=", 1)[1])
    return None
