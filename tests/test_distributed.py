"""Two-process jax.distributed pod-mode test (SURVEY §2c multi-host).

Spawns two real OS processes on the CPU platform, each calling
``parallel/distributed.py::init_pod`` against a localhost coordinator,
builds the global 2-device mesh, and asserts a cross-process ``psum``
reduces over BOTH processes' values — the DCN-equivalent collective path
exercised for real rather than via the single-process fallback.

The subprocesses run outside the parent's jax runtime (the parent's CPU
platform is already initialized with 8 virtual devices; children get one
CPU device each).
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

_CHILD = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, os.environ["_REPO"])
    import jax
    jax.config.update("jax_platforms", "cpu")

    from chiaswarm_tpu.parallel.distributed import (
        init_pod, is_multi_host, local_data_shard,
    )

    pid = int(os.environ["PROCESS_ID"])
    init_pod()  # env contract: COORDINATOR_ADDRESS/NUM_PROCESSES/PROCESS_ID

    assert jax.process_count() == 2, jax.process_count()
    assert jax.process_index() == pid, (jax.process_index(), pid)
    assert is_multi_host()
    assert local_data_shard(8) == (pid * 4, 4)

    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = np.asarray(jax.devices())  # 2 global devices, 1 per process
    assert len(devices) == 2, devices
    mesh = Mesh(devices.reshape(2), ("data",))

    # each process contributes its own value; psum must see both
    local = jnp.full((1, 4), float(pid + 1))
    arr = jax.make_array_from_single_device_arrays(
        (2, 4), NamedSharding(mesh, P("data", None)),
        [jax.device_put(local, jax.local_devices()[0])])

    # global sum over the process-spanning array — XLA inserts the
    # cross-process all-reduce (the DCN collective path in production)
    s = float(jax.jit(jnp.sum)(arr))
    assert s == (1.0 + 2.0) * 4, s

    # explicit psum through shard_map over the global mesh
    from chiaswarm_tpu.core.compat import shard_map
    ps = shard_map(
        lambda v: jax.lax.psum(v, "data"), mesh=mesh,
        in_specs=P("data", None), out_specs=P(None, None),
    )
    tot = jax.jit(ps)(arr)
    local_tot = np.asarray(
        [sh.data for sh in tot.addressable_shards][0])
    assert (local_tot == 3.0).all(), local_tot
    print(f"OK process {pid}: global sum {s}")
""")


@pytest.mark.skipif(os.environ.get("CHIASWARM_SKIP_MULTIPROC") == "1",
                    reason="multi-process test disabled")
def test_two_process_pod_psum(tmp_path):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    repo = str(Path(__file__).resolve().parent.parent)
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)             # 1 CPU device per process
        env.update({
            "_REPO": repo,
            "JAX_PLATFORMS": "cpu",
            "COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
            "NUM_PROCESSES": "2",
            "PROCESS_ID": str(pid),
        })
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _CHILD], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))

    outputs = []
    for pid, proc in enumerate(procs):
        try:
            out, _ = proc.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for p in procs:
                p.kill()
            pytest.fail(f"process {pid} timed out")
        outputs.append(out)
    for pid, (proc, out) in enumerate(zip(procs, outputs)):
        assert proc.returncode == 0, f"process {pid} failed:\n{out}"
        assert f"OK process {pid}" in out
