#!/usr/bin/env python3
"""Compile the cells' device programs for a described v5e, off the chip.

    JAX_PLATFORMS=cpu python3 perfbench/crosscompile.py [config ...]

Run by hand in the sandbox (the third rehearsal of the on-chip-measurement
guide; a whole SDXL step compiles for a minute or more, so this is no
tier-1 test). For each configuration of the diffusion kind it lowers,
for one described ``v5e`` chip: the lane step program at the cells' lane
widths, the batch-1 decode and encode, the seeded-weights fill, and the plain
reference's step and decode. It prints ``memory_analysis()`` of each and
the reckoning the ``model-configs`` floor needs: resident weights plus
the widest lane step's or the decode's temporaries, as a share of the
chip's memory. Nothing runs; no number printed here is a device metric.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

#: lane widths the cells reach, by configuration (PERF.md, Cells): a lone
#: job rides width 1 (both cells today); the wider ones are what a
#: backlog would grow the lane to
LANE_WIDTHS = {"sdxl-1024": (1, 2, 4), "sd15-512": (1, 2, 4, 8, 16)}


class Lowered:
    """Stands in for ``toplevel_jit``: a call with abstract arguments
    compiles for their (described) device and keeps the executable."""

    made: list = []

    def __init__(self, fn, **kwargs):
        import jax

        self.jitted = jax.jit(fn, **kwargs)

    def __call__(self, *args):
        compiled = self.jitted.lower(*args).compile()
        Lowered.made.append(compiled)
        return compiled


def mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {"arguments": m.argument_size_in_bytes,
            "outputs": m.output_size_in_bytes,
            "temporaries": m.temp_size_in_bytes,
            "code": m.generated_code_size_in_bytes}


def main(names) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    from chiaswarm_tpu.models.clip import ClipTextEncoder
    from chiaswarm_tpu.models.configs import FAMILIES
    from chiaswarm_tpu.models.tokenizer import HashTokenizer
    from chiaswarm_tpu.models.unet import UNet
    from chiaswarm_tpu.models.vae import AutoencoderKL
    from chiaswarm_tpu.pipelines import diffusion as diffusion_mod
    from chiaswarm_tpu.pipelines.components import (
        Components,
        abstract_params,
    )
    from chiaswarm_tpu.schedulers import resolve

    from perfbench import reference, weights

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=chip)

    diffusion_mod.toplevel_jit = Lowered
    # the program picks its attention kernel by jax.default_backend();
    # nothing is attached here, so say what the described chip would
    jax.default_backend = lambda: "tpu"
    for name in names:
        config = json.loads(
            (ROOT / "perfbench" / "configs" / f"{name}.json").read_text())
        family = FAMILIES[config["program_family"]]
        serving = config["serving"]
        h, w = serving["height"], serving["width"]
        dtype = jnp.dtype(serving["dtype"])
        shapes = jax.tree.map(
            lambda s: spec(s.shape, dtype if s.dtype == jnp.float32
                           else s.dtype), abstract_params(family))
        resident = sum(s.size * s.dtype.itemsize
                       for s in jax.tree.leaves(shapes))
        out = {"resident_weights": resident}
        # the program's Components around abstract weights
        components = Components(
            family=family, model_name=name,
            tokenizers=[HashTokenizer(c.vocab_size,
                                      c.max_position_embeddings,
                                      c.eos_token_id)
                        for c in family.text_encoders],
            text_encoders=[ClipTextEncoder(c) for c in family.text_encoders],
            unet=UNet(family.unet), vae=AutoencoderKL(family.vae),
            params=shapes)
        pipe = diffusion_mod.DiffusionPipeline(components)
        lh, lw = pipe._latent_hw(h, w)
        ch = family.vae.latent_channels
        ctx_dim = family.unet.cross_attention_dim
        n_tok = family.text_encoders[0].max_position_embeddings
        pooled_dim = family.unet.addition_pooled_dim
        cap = 32
        sampler = resolve(None, prediction_type=family.prediction_type)
        for width in LANE_WIDTHS[name]:
            rows = spec((width, lh, lw, ch), jnp.float32)
            pooled = (spec((width, pooled_dim), jnp.float32)
                      if pooled_dim else spec((1,), jnp.float32))
            args = (
                shapes, spec((width, n_tok, ctx_dim), jnp.float32),
                spec((width, n_tok, ctx_dim), jnp.float32), pooled, pooled,
                rows, spec((width, 2), jnp.uint32),
                spec((width,), jnp.int32), spec((width,), jnp.int32),
                spec((width, cap + 1), jnp.float32),
                spec((width, cap), jnp.float32),
                spec((width,), jnp.float32), rows,
                spec((width,), jnp.bool_), rows,
                spec((width, lh, lw, 1), jnp.float32),
                spec((width,), jnp.bool_),
                {"zero": spec((1,), jnp.float32)},
                spec((1,), jnp.float32), spec((width,), jnp.float32))
            fn = pipe.stepper_step_fn(batch=width, height=h, width=w,
                                      steps_cap=cap, sampler=sampler)
            out[f"lane_step_w{width}"] = mem(fn(*args))
            print(name, f"lane_step_w{width}", out[f"lane_step_w{width}"],
                  flush=True)
        decode = pipe.stepper_decode_fn(batch=1, height=h, width=w)
        out["decode_b1"] = mem(decode(shapes,
                                      spec((1, lh, lw, ch), jnp.float32)))
        ids = [spec((1, n_tok), jnp.int32) for _ in family.text_encoders]
        out["encode_b1"] = mem(pipe.stepper_encode_fn(batch=1)(
            shapes, ids, ids))
        # the benchmark's own device programs
        fill = weights.fill_fn(abstract_params(family), serving["dtype"])
        out["weights_fill"] = mem(jax.jit(fill, out_shardings=chip).lower(
            spec((2,), jnp.uint32)).compile())
        encode, step, decode_ref = reference._programs(
            json.dumps(config, sort_keys=True), "float32")
        x = spec((1, lh, lw, ch), jnp.float32)
        scalar = spec((), jnp.float32)
        added = None
        if pooled_dim:
            added = (spec((2, 6), jnp.float32),
                     spec((2, pooled_dim), jnp.float32))
        out["reference_step"] = mem(step.lower(
            shapes, x, spec((2, n_tok, ctx_dim), jnp.float32), added,
            scalar, scalar, scalar).compile())
        out["reference_decode"] = mem(decode_ref.lower(shapes, x).compile())
        widest = max(out[k]["temporaries"] for k in out
                     if k.startswith(("lane_step", "decode")))
        out["reckoned_peak"] = resident + widest
        out["share_of_16GiB_pct"] = round(
            100.0 * (resident + widest) / (16 * 2 ** 30), 1)
        print(json.dumps({name: out}, indent=1), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:] or list(LANE_WIDTHS)))
