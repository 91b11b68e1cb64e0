"""Model component bundles: modules + params + tokenizers for one checkpoint.

The reference builds a diffusers pipeline object per job from the HF cache
(swarm/diffusion/diffusion_func.py:41-46). The TPU equivalent is a
:class:`Components` bundle that stays resident (core/compile_cache.py): the
Flax modules are cheap static descriptions; the params live on device.

Construction paths:
- :meth:`Components.random` — seeded random weights for hermetic tests,
  benchmarks and the chip smoke (weights don't change FLOPs), materialized
  on the host (:func:`materialize_host`) at every width.
- :meth:`Components.from_checkpoint` — converted torch/safetensors weights
  via chiaswarm_tpu.convert (the initialize-time warm cache replacing
  swarm/initialize.py:62-94).
"""

from __future__ import annotations

import dataclasses
import functools
from pathlib import Path
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from chiaswarm_tpu.models.clip import ClipTextEncoder
from chiaswarm_tpu.models.configs import FAMILIES, ModelFamily, get_family
from chiaswarm_tpu.models.tokenizer import HashTokenizer, Tokenizer, load_tokenizer
from chiaswarm_tpu.models.unet import UNet
from chiaswarm_tpu.models.vae import AutoencoderKL


def param_count(shape_tree) -> int:
    import numpy as np

    return sum(int(np.prod(leaf.shape))
               for leaf in jax.tree.leaves(shape_tree))


def materialize_host(shape_tree, rng, dtype: str = "bfloat16"):
    """Materialize an ``eval_shape`` param tree as seeded host-numpy
    values — no XLA program, nothing on the device until the caller
    places the tree. Every leaf is non-zero (a zero conv or projection
    outputs zero whatever the kernel under it does, so a broken kernel
    would pass any output check), scaled so a 30-step bf16 denoise stays
    finite at published widths:

    - ``kernel``: uniform with variance 1/fan_in (flax's lecun scaling;
      fan_in = every axis but the last), so activations keep O(1)
      magnitude through hundreds of layers — N(0, 0.02) would not at
      fan-ins of 10^3..10^4;
    - ``scale`` (norm gains): ones;
    - everything else (biases, embedding tables, mix factors): uniform
      with standard deviation 0.02.

    ``rng`` is a ``numpy.random.Generator``; each leaf draws from its
    own spawned child, so the values depend on the seed and the tree
    alone, not on how the fill is threaded. Uniform, not normal: the
    first two moments are what the scaling argument needs, and it
    samples twice as fast (SDXL is 3.5 G draws)."""
    import math
    import os
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    out_dtype = jnp.dtype(dtype)
    paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(shape_tree)

    def fill(args):
        (path, s), child = args
        dt = out_dtype if s.dtype == jnp.float32 else s.dtype
        name = getattr(path[-1], "key", None) if path else None
        if name == "scale":
            return np.ones(s.shape, dt)
        if name == "kernel" and len(s.shape) >= 2:
            std = 1.0 / math.sqrt(math.prod(s.shape[:-1]))
        else:
            std = 0.02
        x = child.random(s.shape, dtype=np.float32)
        x -= np.float32(0.5)
        x *= np.float32(std * math.sqrt(12.0))
        return x.astype(dt)

    jobs = zip(paths_leaves, rng.spawn(len(paths_leaves)))
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        return jax.tree_util.tree_unflatten(treedef, list(pool.map(fill, jobs)))


def abstract_params(family: ModelFamily | str) -> dict[str, Any]:
    """Param SHAPE trees for every module of a family — pure
    ``jax.eval_shape`` tracing, no arrays and no compile (cached per
    family: SDXL takes seconds to trace). Drives random-weight
    materialization and the mesh policy's size estimate."""
    if isinstance(family, str):
        family = FAMILIES[family]
    return _trace_abstract_params(family)


@functools.lru_cache(maxsize=None)
def _trace_abstract_params(family: ModelFamily) -> dict[str, Any]:
    text_encoders = [ClipTextEncoder(cfg) for cfg in family.text_encoders]
    unet = UNet(family.unet)
    vae = AutoencoderKL(family.vae)

    key = jax.random.PRNGKey(0)
    ids = jnp.zeros((1, family.text_encoders[0].max_position_embeddings),
                    jnp.int32)
    shapes: dict[str, Any] = {}
    for i, te in enumerate(text_encoders):
        shapes[f"text_encoder_{i}"] = jax.eval_shape(te.init, key, ids)
    latent = jnp.zeros((1, 8, 8, family.unet.sample_channels))
    ctx = jnp.zeros((1, ids.shape[1], family.unet.cross_attention_dim))
    added = None
    if family.unet.addition_embed_dim is not None:
        added = {
            "time_ids": jnp.zeros((1, 6)),
            "text_embeds": jnp.zeros((1, family.unet.addition_pooled_dim)),
        }
    labels = (jnp.zeros((1,), jnp.int32)
              if family.unet.num_class_embeds is not None else None)
    shapes["unet"] = jax.eval_shape(
        lambda k, s, t, c, a, cl: unet.init(k, s, t, c, a, class_labels=cl),
        key, latent, jnp.zeros((1,)), ctx, added, labels)
    shapes["vae"] = jax.eval_shape(
        vae.init, key, jnp.zeros((1, 16, 16, family.vae.in_channels)))
    return shapes


def measured_param_bytes(tree: Any) -> int:
    """MEASURED per-chip HBM footprint of a live param tree (ISSUE 8):
    sum each leaf's ``.nbytes`` across its addressable shards, bucketed
    per device, max over devices — replicated copies cost every chip
    their full size, tensor-parallel shards split it. This is what the
    residency ledger (serving/residency.py) accounts with, replacing
    the worker's bf16 family-size estimate. Host/numpy leaves (not yet
    placed) count toward a shared bucket. int8-quantized leaves
    (convert/quantize.py Int8Param pytree nodes) flatten to their code
    + scale arrays, so the measurement sees the real int8 bytes."""
    per_device: dict[Any, int] = {}
    host_bytes = 0
    for leaf in jax.tree.leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if shards:
            for shard in shards:
                nbytes = int(getattr(shard.data, "nbytes", 0) or 0)
                per_device[shard.device] = (
                    per_device.get(shard.device, 0) + nbytes)
        else:
            nbytes = getattr(leaf, "nbytes", None)
            if nbytes is None:
                import numpy as np

                nbytes = np.asarray(leaf).nbytes
            host_bytes += int(nbytes)
    if not per_device:
        return host_bytes
    return max(per_device.values()) + host_bytes


def estimate_family_bytes(family: ModelFamily | str,
                          bytes_per_param: int = 2) -> int:
    """Serving-footprint estimate (bf16 by default) for one family's full
    param set — from abstract shapes, so big families cost a trace, not
    memory. Used by the worker's default dp x tp policy (core/mesh.py)."""
    if isinstance(family, str):
        family = FAMILIES[family]
    return param_count(abstract_params(family)) * bytes_per_param


@dataclasses.dataclass
class Components:
    family: ModelFamily
    model_name: str
    tokenizers: Sequence[Tokenizer]
    text_encoders: Sequence[ClipTextEncoder]
    unet: UNet
    vae: AutoencoderKL
    params: dict[str, Any]  # keys: text_encoder_{i}, unet, vae

    @classmethod
    def random(cls, family: ModelFamily | str, seed: int = 0,
               model_name: str | None = None,
               dtype: str = "bfloat16") -> "Components":
        """Seeded random components — what the registry serves under
        ``allow_random`` — built WITHOUT running any XLA program: module
        param shapes come from ``jax.eval_shape`` (abstract tracing) and
        the values from host numpy (:func:`materialize_host`). One path
        for every width: flax's own jitted fp32 init would, at SDXL
        width, exhaust a single chip's HBM and compile for minutes,
        where this takes well under a minute and leaves FLOPs and memory
        traffic identical to a converted checkpoint's. The params stay
        on the HOST, as ``from_checkpoint``'s do — the registry (or the
        caller) places them."""
        import numpy as np

        if isinstance(family, str):
            family = FAMILIES[family]
        text_encoders = [ClipTextEncoder(cfg) for cfg in family.text_encoders]
        tokenizers = [
            HashTokenizer(cfg.vocab_size, cfg.max_position_embeddings,
                          cfg.eos_token_id)
            for cfg in family.text_encoders
        ]
        unet = UNet(family.unet)
        vae = AutoencoderKL(family.vae)

        rng = np.random.default_rng(seed)
        shapes = abstract_params(family)
        params = {module: materialize_host(tree, rng, dtype)
                  for module, tree in shapes.items()}
        return cls(
            family=family,
            model_name=model_name or f"random/{family.name}",
            tokenizers=tokenizers,
            text_encoders=text_encoders,
            unet=unet,
            vae=vae,
            params=params,
        )

    @classmethod
    def from_checkpoint(cls, checkpoint_dir: str | Path,
                        model_name: str | None = None,
                        family: ModelFamily | str | None = None) -> "Components":
        from chiaswarm_tpu.convert.torch_to_flax import load_checkpoint

        checkpoint_dir = Path(checkpoint_dir)
        model_name = model_name or checkpoint_dir.name
        if family is None:
            family = get_family(model_name)
        elif isinstance(family, str):
            family = FAMILIES[family]
        params = load_checkpoint(checkpoint_dir, family)
        text_encoders = [ClipTextEncoder(cfg) for cfg in family.text_encoders]
        tokenizers = [
            load_tokenizer(checkpoint_dir, cfg.vocab_size, cfg.eos_token_id,
                           cfg.max_position_embeddings)
            for cfg in family.text_encoders
        ]
        return cls(
            family=family,
            model_name=model_name,
            tokenizers=tokenizers,
            text_encoders=text_encoders,
            unet=UNet(family.unet),
            vae=AutoencoderKL(family.vae),
            params=params,
        )

    def param_bytes(self) -> int:
        return measured_param_bytes(self.params)


@dataclasses.dataclass
class ControlNetBundle:
    """A ControlNet checkpoint attachable to a base-family pipeline.

    The reference loads a ``ControlNetModel`` next to the pipeline per job
    (swarm/diffusion/diffusion_func.py:29-34); here the bundle is resident
    and LRU-cached like every other param tree (node/registry.py). The
    ``params`` dict holds two trees: ``net`` (the control branch) and
    ``embed`` (the conditioning-image hint encoder, hoisted out of the
    denoise scan by the pipeline).
    """

    family: ModelFamily
    model_name: str
    params: dict[str, Any]  # keys: net, embed

    @classmethod
    def random(cls, family: ModelFamily | str, seed: int = 0,
               model_name: str | None = None,
               dtype: str = "bfloat16") -> "ControlNetBundle":
        """Seeded random bundle, host-materialized like
        :meth:`Components.random`. The output ("zero") convs come out
        NON-zero, unlike an untrained ControlNet's: a random bundle
        stands in for a trained checkpoint, to exercise the branch — a
        zero head would hide whatever runs under it."""
        import numpy as np

        from chiaswarm_tpu.models.controlnet import (
            ControlCondEmbedding,
            ControlNet,
        )

        if isinstance(family, str):
            family = FAMILIES[family]
        cfg = family.unet
        net = ControlNet(cfg)
        embed = ControlCondEmbedding(cfg.block_out_channels[0],
                                     downscale=family.vae.downscale)
        f = family.vae.downscale
        lh = lw = 8
        latent = jnp.zeros((1, lh, lw, cfg.sample_channels), jnp.float32)
        cond = jnp.zeros((1, lh * f, lw * f, 3), jnp.float32)
        ctx = jnp.zeros((1, 77, cfg.cross_attention_dim), jnp.float32)
        added = None
        if cfg.addition_embed_dim is not None:
            added = {
                "time_ids": jnp.zeros((1, 6), jnp.float32),
                "text_embeds": jnp.zeros(
                    (1, cfg.addition_pooled_dim), jnp.float32),
            }
        rng = np.random.default_rng(seed)
        key = jax.random.PRNGKey(0)
        params = {"embed": materialize_host(
            jax.eval_shape(embed.init, key, cond), rng, dtype)}
        cond_emb_shape = jax.eval_shape(
            lambda p, c: embed.apply(p, c), params["embed"], cond)
        cond_emb = jnp.zeros(cond_emb_shape.shape, cond_emb_shape.dtype)
        params["net"] = materialize_host(
            jax.eval_shape(net.init, key, latent, jnp.zeros((1,)), ctx,
                           cond_emb, added), rng, dtype)
        return cls(family=family,
                   model_name=model_name or f"random/controlnet-{family.name}",
                   params=params)

    @classmethod
    def from_checkpoint(cls, checkpoint_dir: str | Path,
                        model_name: str | None = None,
                        family: ModelFamily | str | None = None,
                        ) -> "ControlNetBundle":
        from chiaswarm_tpu.convert.torch_to_flax import (
            convert_controlnet,
            read_torch_weights,
        )

        checkpoint_dir = Path(checkpoint_dir)
        if (checkpoint_dir / "controlnet").is_dir():  # full pipeline snapshot
            checkpoint_dir = checkpoint_dir / "controlnet"
        model_name = model_name or checkpoint_dir.name
        if family is None:
            family = get_family(model_name)
        elif isinstance(family, str):
            family = FAMILIES[family]
        state = read_torch_weights(checkpoint_dir)
        return cls(family=family, model_name=model_name,
                   params=convert_controlnet(state, family.unet))

    def param_bytes(self) -> int:
        return measured_param_bytes(self.params)
