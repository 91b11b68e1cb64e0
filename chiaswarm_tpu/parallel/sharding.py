"""GSPMD tensor-parallel partition rules for the model zoo.

The reference never shards a model — each job's weights live wholly on one
GPU (`pipeline.to("cuda:N")`, swarm/diffusion/diffusion_func.py:46). For
models larger than one chip's HBM (SDXL at high batch, cascades, video) the
TPU-native answer is Megatron-style tensor parallelism expressed purely as
*weight sharding annotations*: we lay out the attention/MLP projection
matrices over the ``model`` mesh axis and let GSPMD insert the collectives
(all-gather/reduce-scatter over ICI) during compilation.

Column/row pattern per transformer block (so the pair needs only ONE
all-reduce on the residual, not per-matmul gathers):

- q/k/v projections, MLP up-projection: column-parallel — kernel
  P(None, "model"), bias P("model"): each chip computes its head slice.
- output projection, MLP down-projection: row-parallel — kernel
  P("model", None), bias replicated; GSPMD emits the psum.

Resnet conv pairs follow the same pattern on their CHANNEL dims (no halo
needed — a 1x1-style channel split, not spatial): ``conv1`` is
column-parallel on output channels (with ``time_emb_proj`` and ``norm2``
sharded to match, group stats staying shard-local because tp divides the
32 GroupNorm groups), ``conv2`` is row-parallel on input channels, and
GSPMD emits one psum per resnet block on the residual. Most of an
SD-class UNet's FLOPs are in its convs (the share on this chip is not
measured: ROADMAP S7), so leaving them replicated leaves most of the
work unsplit under tp; with the resnet pairs sharded the
per-device FLOPs fraction drops to ~1/(dp*tp) + small residue (conv_in/
out, shortcuts, up/downsamples — measured by dryrun_multichip).

Contraction-dim (row-parallel) sharding for the channel-square stragglers
(r5, VERDICT r4 #4): the SpatialTransformer/TemporalTransformer
proj_in/proj_out (linear OR 1x1-conv spelling), resnet shortcut convs,
and the up/downsample resize convs all consume a REPLICATED activation
and feed a norm or residual that needs full channels again — so the
profitable layout is splitting the input-channel contraction across
``model`` and letting GSPMD emit one psum per op: FLOPs/tp at the cost
of a single all-reduce, with no layout change for producers/consumers.

Still replicated: norms on replicated activations, embeddings, time
MLPs, conv_in/conv_out (4-channel ends — nothing to split). This matches
the scaling-book recipe: annotate the big matmuls, let the compiler
place collectives, profile, iterate.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chiaswarm_tpu.core.mesh import MODEL_AXIS

# column-parallel producers (output dim sharded) and row-parallel consumers
# (input dim sharded); names cover the UNet (to_q/.../ff), the CLIP towers
# (q_proj/.../fc1/fc2) and the VAE mid-attention.
_COLUMN = frozenset({"to_q", "to_k", "to_v", "q_proj", "k_proj", "v_proj",
                     "fc1"})
_ROW = frozenset({"to_out", "out_proj", "fc2"})
_MLP_GLU_UP = "proj_in"     # GEGLU up-projection inside FeedForward ("ff")
_MLP_DOWN = "proj_out"


def _in_resnet(path: tuple[str, ...]) -> bool:
    """Inside a UNet/ControlNet ResnetBlock (down_*_resnets_*,
    mid_resnets_*, up_*_resnets_* — models/unet.py naming). VAE resnets
    share those block names but nest under encoder/decoder submodules and
    are excluded: the VAE is a tiny FLOPs fraction and its small channel
    counts don't divide cleanly across model shards."""
    return (any("resnets" in part for part in path)
            and not any(part in ("encoder", "decoder") for part in path))


def _spec_for(path: tuple[str, ...], ndim: int) -> P:
    if ndim == 0 or not path:
        return P()
    leaf = path[-1]
    parent = path[-2] if len(path) >= 2 else ""
    grandparent = path[-3] if len(path) >= 3 else ""
    # "ff" is the TransformerBlock MLP; "ff_in" is the SVD temporal
    # block's input MLP — same GEGLU pair, same column/row layout
    in_ff = (parent in ("ff", "ff_in")
             or grandparent in ("ff", "ff_in"))

    column = parent in _COLUMN or (in_ff and parent == _MLP_GLU_UP)
    row = parent in _ROW or (in_ff and parent == _MLP_DOWN)

    if leaf == "kernel" and ndim == 2:
        if column:
            return P(None, MODEL_AXIS)
        if row:
            return P(MODEL_AXIS, None)
    if leaf == "bias" and ndim == 1 and column:
        return P(MODEL_AXIS)

    # module-level proj_in/proj_out (SpatialTransformer and the video
    # transformers — NOT the FeedForward pair handled above): plain
    # channel matmuls between a replicated activation and a norm/residual
    # that needs full channels — shard the contraction dim, GSPMD emits
    # one psum (r5; the exclusion this replaces was the last double-digit
    # tp residue: 0.141 vs 0.125 ideal in the r4 virtual-mesh dry run,
    # record deleted in PR 21)
    if not in_ff and parent in ("proj_in", "proj_out") and leaf == "kernel":
        if ndim == 2:
            return P(MODEL_AXIS, None)
        if ndim == 4:          # the 1x1-conv spelling (SD1.5-class)
            return P(None, None, MODEL_AXIS, None)

    # up/downsample resize convs (UNet modules wrap the conv in a
    # ``conv`` submodule; the VAE's bare-conv spelling stays replicated)
    if parent == "conv" and leaf == "kernel" and ndim == 4 and \
            ("downsample" in grandparent or "upsample" in grandparent):
        return P(None, None, MODEL_AXIS, None)

    # resnet conv pair: channel-wise Megatron (conv1 output channels /
    # conv2 input channels), with the in-between time projection and
    # GroupNorm sharded to match
    if _in_resnet(path):
        if parent == "conv1":
            if leaf == "kernel" and ndim == 4:   # HWIO, O sharded
                return P(None, None, None, MODEL_AXIS)
            if leaf == "bias" and ndim == 1:
                return P(MODEL_AXIS)
        if parent == "conv2" and leaf == "kernel" and ndim == 4:
            return P(None, None, MODEL_AXIS, None)  # I sharded (row)
        if parent == "time_emb_proj":
            if leaf == "kernel" and ndim == 2:
                return P(None, MODEL_AXIS)
            if leaf == "bias" and ndim == 1:
                return P(MODEL_AXIS)
        if parent == "norm2" and ndim == 1:      # scale/bias over conv1 out
            return P(MODEL_AXIS)
        if parent == "conv_shortcut" and leaf == "kernel" and ndim == 4:
            # 1x1 channel-change conv off the replicated block input:
            # contraction-dim split + psum, like proj_in/proj_out
            return P(None, None, MODEL_AXIS, None)
    return P()  # replicated: norms, embeddings, time MLPs, conv_in/out


def param_partition_specs(params: Any) -> Any:
    """PartitionSpec pytree matching ``params`` (Components.params or any
    sub-tree)."""

    def spec(path, leaf) -> P:
        names = tuple(
            k.key if hasattr(k, "key") else str(k) for k in path
        )
        return _spec_for(names, getattr(leaf, "ndim", 0))

    return jax.tree_util.tree_map_with_path(spec, params)


def param_shardings(params: Any, mesh: Mesh) -> Any:
    """NamedSharding pytree for ``params`` on ``mesh``."""
    specs = param_partition_specs(params)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_params(params: Any, mesh: Mesh) -> Any:
    """Place ``params`` onto ``mesh`` according to the partition rules.

    With |model| = 1 every spec degenerates to replication, so single-chip
    and multi-chip share one code path (same stance as
    core/mesh.py:single_device_mesh).
    """
    shardings = param_shardings(params, mesh)
    return jax.tree.map(jax.device_put, params, shardings)
