"""Parallelism layer: tensor-parallel sharding rules, ring attention for
sequence/context parallelism, and multi-host distributed init.

The reference's only parallelism is job-level data parallelism across
isolated GPUs (swarm/worker.py:40-47,113-128; SURVEY.md §2b). On TPU the
pod is one SPMD machine, so this layer adds what the reference never had:

- data parallel: batch sharded on the ``data`` mesh axis (free for inference)
- tensor parallel: attention/MLP weight sharding on ``model`` via GSPMD
  partition rules (parallel/sharding.py)
- sequence/context parallel: ring attention over the ``seq`` axis with
  `ppermute` KV rotation on ICI (parallel/ring_attention.py)
- multi-host: `jax.distributed.initialize` wrapper (parallel/distributed.py)
"""

from chiaswarm_tpu.parallel.context import active_seq_mesh, param_mesh
from chiaswarm_tpu.parallel.ring_attention import ring_attention
from chiaswarm_tpu.parallel.sharding import (
    param_partition_specs,
    param_shardings,
    shard_params,
)

__all__ = [
    "active_seq_mesh",
    "ring_attention",
    "param_partition_specs",
    "param_shardings",
    "param_mesh",
    "shard_params",
]
