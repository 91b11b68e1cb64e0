"""Cascaded pixel-space diffusion (DeepFloyd-IF-class models).

Capability parity with swarm/diffusion/diffusion_func_if.py:14-92: a
three-stage cascade — 64px T5-conditioned base, 4x super-resolution to
256px, then a final upscale to ~1024px — with the prompt embedding computed
ONCE and shared across stages (:45-61; the reference re-encodes on stage 1
and passes embeds down).

TPU-first redesign:
- stages 1 and 2 are each ONE jitted program (text encode is hoisted out
  and shared; denoise is a lax.scan; no VAE — pixel space);
- stage 2 conditions by channel-concatenating the nearest-upsampled stage-1
  output (sample_channels = 6), the same concat-conditioning pattern as the
  latent upscaler;
- the UNets predict epsilon + learned variance (out_channels = 6); the
  sigma-space samplers consume the epsilon half;
- stage 3 runs the jitted SD-x4-upscaler (pipelines/upscale.py::
  Upscale4xPipeline) — the SAME text-conditioned x4 SR model class the
  reference uses (diffusion_func_if.py:31-40), 256 -> 1024 in one pass;
  the pass loop also accepts an x2-class upscaler (two passes) for nodes
  without the x4 checkpoint.

The reference's known stage-2 bug (negative_prompt fed from ``prompt``,
diffusion_func_if.py:44) is intentionally NOT reproduced.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from chiaswarm_tpu.core.compile_cache import (
    toplevel_jit,
    GLOBAL_CACHE,
    bucket_batch,
    static_cache_key,
)
from chiaswarm_tpu.parallel.context import param_mesh_wrap
from chiaswarm_tpu.core.rng import key_for_seed
from chiaswarm_tpu.models.common import upsample2x_nearest
from chiaswarm_tpu.models.configs import UNetConfig
from chiaswarm_tpu.models.t5 import T5Config, T5Encoder
from chiaswarm_tpu.models.tokenizer import HashTokenizer
from chiaswarm_tpu.models.unet import UNet
from chiaswarm_tpu.schedulers import (
    make_noise_schedule,
    make_sampling_schedule,
    resolve,
    sampler_step,
    scale_model_input,
)
from chiaswarm_tpu.schedulers.common import ScheduleConfig
from chiaswarm_tpu.schedulers.sampling import init_sampler_state


@dataclasses.dataclass(frozen=True)
class CascadeFamily:
    """Architecture of one IF-class cascade (base + super-res stages)."""

    name: str
    t5: T5Config
    stage1: UNetConfig          # base: sample_channels=3, out_channels=6
    stage2: UNetConfig          # super-res: sample_channels=6, out_channels=6
    base_size: int = 64
    sr_size: int = 256
    beta_schedule: str = "squaredcos_cap_v2"  # IF trains on a cosine schedule


# IF-I-XL / IF-II-L shaped (DeepFloyd/IF-I-XL-v1.0 + IF-II-L-v1.0)
IF_XL = CascadeFamily(
    name="if_xl",
    t5=T5Config(),
    stage1=UNetConfig(
        sample_channels=3, out_channels=6,
        block_out_channels=(192, 384, 768, 1536),
        transformer_depth=(0, 0, 1, 1),
        attention_head_dim=64, head_dim_is_count=False,
        cross_attention_dim=4096,
    ),
    stage2=UNetConfig(
        sample_channels=6, out_channels=6,
        block_out_channels=(128, 256, 512, 1024),
        transformer_depth=(0, 0, 1, 1),
        attention_head_dim=64, head_dim_is_count=False,
        cross_attention_dim=4096,
    ),
)

# Hermetic-test cascade: full structure, toy widths.
TINY_CASCADE = CascadeFamily(
    name="tiny_cascade",
    t5=T5Config(vocab_size=1000, d_model=32, d_kv=8, d_ff=64,
                num_layers=2, num_heads=4, max_length=77, eos_token_id=999,
                dtype="float32"),
    stage1=UNetConfig(sample_channels=3, out_channels=6,
                      block_out_channels=(32, 64), layers_per_block=1,
                      transformer_depth=(0, 1), attention_head_dim=4,
                      head_dim_is_count=True, cross_attention_dim=32,
                      dtype="float32"),
    stage2=UNetConfig(sample_channels=6, out_channels=6,
                      block_out_channels=(32, 64), layers_per_block=1,
                      transformer_depth=(0, 1), attention_head_dim=4,
                      head_dim_is_count=True, cross_attention_dim=32,
                      dtype="float32"),
    base_size=16,
    sr_size=64,
)

CASCADE_FAMILIES = {f.name: f for f in (IF_XL, TINY_CASCADE)}


def get_cascade_family(model_name: str) -> CascadeFamily:
    low = (model_name or "").lower()
    tail = low.rsplit("/", 1)[-1]
    if low in CASCADE_FAMILIES:
        return CASCADE_FAMILIES[low]
    if tail in CASCADE_FAMILIES:
        return CASCADE_FAMILIES[tail]
    return CASCADE_FAMILIES["if_xl"]


@dataclasses.dataclass
class CascadeComponents:
    family: CascadeFamily
    model_name: str
    tokenizer: Any
    t5: T5Encoder
    unet1: UNet
    unet2: UNet
    params: dict[str, Any]  # keys: t5, unet1, unet2

    @classmethod
    def random(cls, family: CascadeFamily | str, seed: int = 0,
               model_name: str | None = None) -> "CascadeComponents":
        if isinstance(family, str):
            family = CASCADE_FAMILIES[family]
        key = jax.random.PRNGKey(seed)
        t5 = T5Encoder(family.t5)
        unet1 = UNet(family.stage1)
        unet2 = UNet(family.stage2)
        tokenizer = HashTokenizer(family.t5.vocab_size, family.t5.max_length,
                                  family.t5.eos_token_id,
                                  pad_id=family.t5.pad_token_id,
                                  add_bos=False)
        ids = jnp.zeros((1, family.t5.max_length), jnp.int32)
        key, k1, k2, k3 = jax.random.split(key, 4)
        params = {"t5": jax.jit(t5.init)(k1, ids)}
        ctx = jnp.zeros((1, family.t5.max_length, family.t5.d_model),
                        jnp.float32)
        s = 8
        params["unet1"] = jax.jit(unet1.init)(
            k2, jnp.zeros((1, s, s, family.stage1.sample_channels)),
            jnp.zeros((1,)), ctx)
        params["unet2"] = jax.jit(unet2.init)(
            k3, jnp.zeros((1, s, s, family.stage2.sample_channels)),
            jnp.zeros((1,)), ctx)
        return cls(family=family,
                   model_name=model_name or f"random/{family.name}",
                   tokenizer=tokenizer, t5=t5, unet1=unet1, unet2=unet2,
                   params=params)

    def param_bytes(self) -> int:
        leaves = jax.tree.leaves(self.params)
        return sum(leaf.size * leaf.dtype.itemsize for leaf in leaves)


class CascadePipeline:
    """Resident compile-cached IF-class cascade executor."""

    def __init__(self, components: CascadeComponents,
                 attn_impl: str = "auto") -> None:
        self.c = components
        fam = components.family
        if attn_impl != "auto":
            if attn_impl != fam.stage1.attn_impl:
                components.unet1 = UNet(dataclasses.replace(
                    fam.stage1, attn_impl=attn_impl))
            if attn_impl != fam.stage2.attn_impl:
                components.unet2 = UNet(dataclasses.replace(
                    fam.stage2, attn_impl=attn_impl))
        self.schedule_config = ScheduleConfig(
            beta_schedule=fam.beta_schedule,
            prediction_type="epsilon",
        )
        self.noise_schedule = make_noise_schedule(self.schedule_config)

    def _build_fn(self, *, batch: int, steps1: int, steps2: int,
                  sampler, use_cfg: bool):
        fam = self.c.family
        t5, unet1, unet2 = self.c.t5, self.c.unet1, self.c.unet2
        sched1 = make_sampling_schedule(self.noise_schedule, steps1, sampler)
        sched2 = make_sampling_schedule(self.noise_schedule, steps2, sampler)
        s1, s2 = fam.base_size, fam.sr_size
        if s2 % s1 != 0 or (s2 // s1) & (s2 // s1 - 1):
            raise ValueError("sr_size must be a power-of-two multiple of "
                             "base_size")

        def denoise(unet, params, sched, steps, x, ctx, cond, guidance,
                    row_keys):
            """Shared scan: ``cond`` (static None or array) is channel-
            concatenated every step (stage-2 conditioning). ``row_keys``
            is one PRNG key PER batch row — row b's ancestral noise
            depends only on its own key, so an image is identical at any
            batch size (the diffusion pipeline's per-sample contract,
            pipelines/diffusion.py)."""

            def body(carry, i):
                x, state, row_keys = carry
                inp = scale_model_input(sched, x, i)
                if cond is not None:
                    inp = jnp.concatenate([inp, cond], axis=-1)
                if use_cfg:
                    inp2 = jnp.concatenate([inp, inp], axis=0)
                    t2 = sched.timesteps[i][None].repeat(inp2.shape[0], axis=0)
                    out = unet.apply(params, inp2, t2, ctx)
                    eps = out[..., : x.shape[-1]]  # drop learned variance
                    eps_u, eps_c = jnp.split(eps, 2, axis=0)
                    eps = eps_u + guidance * (eps_c - eps_u)
                else:
                    t1 = sched.timesteps[i][None].repeat(x.shape[0], axis=0)
                    out = unet.apply(params, inp, t1, ctx)
                    eps = out[..., : x.shape[-1]]
                both = jax.vmap(jax.random.split)(row_keys)
                row_keys, skeys = both[:, 0], both[:, 1]
                noise = jax.vmap(lambda k: jax.random.normal(
                    k, x.shape[1:], jnp.float32))(skeys)
                x, state = sampler_step(sampler, sched, i, x, eps, state,
                                        noise=noise, start_index=0)
                return (x, state, row_keys), None

            (x, _, _), _ = jax.lax.scan(
                body, (x, init_sampler_state(x), row_keys),
                jnp.arange(steps))
            return x

        def fn(params, ids, neg_ids, row_keys, guidance):
            # the IF serving path hands T5 the tokenizer padding mask
            # (pad id 0) — padding tokens must not shape the prompt embeds
            pad = fam.t5.pad_token_id
            ctx = t5.apply(params["t5"], ids, ids != pad)
            if use_cfg:
                nctx = t5.apply(params["t5"], neg_ids, neg_ids != pad)
                ctx2 = jnp.concatenate([nctx, ctx], axis=0)
            else:
                ctx2 = ctx

            def stage_keys(stage: int):
                return jax.vmap(
                    lambda k: jax.random.fold_in(k, stage))(row_keys)

            # ---- stage 1: 64px base
            x = jax.vmap(lambda k: jax.random.normal(
                k, (s1, s1, 3), jnp.float32))(stage_keys(1))
            x = x * sched1.sigmas[0]
            x = denoise(unet1, params["unet1"], sched1, steps1, x, ctx2,
                        None, guidance, stage_keys(2))
            x = jnp.clip(x, -1.0, 1.0)

            # ---- stage 2: super-res, conditioned on upsampled stage 1
            # (cond is concatenated pre-CFG-doubling inside denoise, so it
            # stays at the plain batch size)
            cond = x
            for _ in range((s2 // s1).bit_length() - 1):
                cond = upsample2x_nearest(cond)
            y = jax.vmap(lambda k: jax.random.normal(
                k, (s2, s2, 3), jnp.float32))(stage_keys(3))
            y = y * sched2.sigmas[0]
            y = denoise(unet2, params["unet2"], sched2, steps2, y, ctx2,
                        cond, guidance, stage_keys(4))
            # quantize ON DEVICE: uint8 moves 4x fewer bytes over the
            # host link (pipelines/diffusion.py rationale)
            return (jnp.clip((y + 1.0) * 127.5 + 0.5, 0.0, 255.0)
                    ).astype(jnp.uint8)

        return param_mesh_wrap(toplevel_jit(fn), self.c.params)

    def _get_fn(self, **static):
        return GLOBAL_CACHE.cached_executable(
            static_cache_key(id(self.c), "cascade", static),
            lambda: self._build_fn(**static))

    def submit(self, prompt: str, negative_prompt: str = "",
               steps: int = 50, sr_steps: int = 30,
               guidance_scale: float = 7.0, batch: int = 1,
               seed: int = 0, scheduler: str | None = None,
               first_row: int = 0):
        """Dispatch the stage-1+2 program WITHOUT blocking on the result.

        Returns ``(device_img, requested, config)`` — the uint8 output is
        still materializing on the chip (jax async dispatch), so a caller
        can queue more work (the next item's stages, another submesh's
        stage 3) before paying the transfer. The blocking path is
        ``__call__``.

        Row b's noise key is ``fold_in(key_for_seed(seed), first_row+b)``
        — the per-sample contract: a (seed, row) pair draws the same
        image whether it runs inside a batch or as a batch-1 program at
        ``first_row=row`` (generate_stage_parallel relies on this)."""
        requested = max(1, batch)
        batch = bucket_batch(requested)
        sampler = resolve(scheduler, prediction_type="epsilon")
        use_cfg = guidance_scale > 1.0
        tok = self.c.tokenizer
        ids = jnp.asarray(tok.encode_batch([prompt] * batch))
        neg = jnp.asarray(tok.encode_batch([negative_prompt or ""] * batch))

        fn = self._get_fn(batch=batch, steps1=int(steps),
                          steps2=int(sr_steps), sampler=sampler,
                          use_cfg=use_cfg)
        base_key = key_for_seed(seed)
        row_keys = jax.vmap(
            lambda r: jax.random.fold_in(base_key, r)
        )(jnp.arange(first_row, first_row + batch))
        img = fn(self.c.params, ids, neg, row_keys,
                 jnp.float32(guidance_scale))
        config = {
            "model_name": self.c.model_name,
            "family": self.c.family.name,
            "mode": "cascade_txt2img",
            "steps": int(steps),
            "sr_steps": int(sr_steps),
            "guidance_scale": float(guidance_scale),
            "size": [self.c.family.sr_size, self.c.family.sr_size],
            "scheduler": sampler.kind,
        }
        return img, requested, config

    def __call__(self, prompt: str, negative_prompt: str = "",
                 steps: int = 50, sr_steps: int = 30,
                 guidance_scale: float = 7.0, batch: int = 1,
                 seed: int = 0, scheduler: str | None = None,
                 upscaler=None, final_size: int | None = None,
                 ) -> tuple[np.ndarray, dict]:
        """Full IF protocol. Stages 1+2 (base -> sr_size) always run; when
        ``upscaler`` (a LatentUpscalePipeline) is provided the cascade runs
        its third stage — repeated x2 latent-upscale denoise passes until
        ``final_size`` (default 4 * sr_size, the reference's x4-upscaler
        output: 256 -> 1024, diffusion_func_if.py:31-40,63-65)."""
        img, requested, config = self.submit(
            prompt, negative_prompt, steps=steps, sr_steps=sr_steps,
            guidance_scale=guidance_scale, batch=batch, seed=seed,
            scheduler=scheduler)
        img_u8 = np.asarray(jax.device_get(img))  # uint8 off-chip
        img_u8 = img_u8[:requested]  # trim the pow2 compile bucket padding
        stages = 2
        if upscaler is not None:
            img_u8, stage3 = _run_stage3(img_u8, upscaler, prompt, seed,
                                         final_size or
                                         self.c.family.sr_size * 4)
            config.update(stage3)
            if "stage3_passes" in stage3:
                stages += 1
            config["size"] = list(img_u8.shape[1:3])
        config["stages"] = stages
        return img_u8, config


def _run_stage3(img_u8: np.ndarray, upscaler, prompt: str, seed: int,
                final_size: int, first_row: int = 0,
                ) -> tuple[np.ndarray, dict]:
    """Stage 3: upscale denoise passes to ``final_size`` (one x4 pass for
    the SD-x4-upscaler; two passes for an x2-class stand-in). The
    reference's stage 3 re-conditions on the raw prompt STRING
    (diffusion_func_if.py:63-65 — the shared T5 embeds stop at stage 2;
    the x4-upscaler is CLIP-conditioned), so passing ``prompt`` down is
    the faithful contract here too."""
    target = int(final_size)
    config: dict = {}
    passes = 0
    prev_size = 0
    # the upscaler buckets its input at 1024 max, so output caps at
    # 2048: stop when a pass makes no progress (else a hive job with an
    # oversized final_size would spin this loop forever)
    while img_u8.shape[1] < target and img_u8.shape[1] > prev_size:
        prev_size = img_u8.shape[1]
        img_u8, up_config = upscaler(img_u8, prompt=prompt or "",
                                     seed=seed, first_row=first_row)
        passes += 1
        config.update(up_config)
    if passes:
        config["stage3_passes"] = passes
    return img_u8, config


def generate_stage_parallel(pipe: CascadePipeline, upscaler, *,
                            prompt: str, negative_prompt: str = "",
                            steps: int = 50, sr_steps: int = 30,
                            guidance_scale: float = 7.0, n_images: int = 1,
                            seed: int = 0, scheduler: str | None = None,
                            final_size: int | None = None,
                            ) -> tuple[np.ndarray, dict]:
    """Pipeline-parallel cascade: stages 1+2 and stage 3 on DISJOINT
    submeshes (core/mesh.py::split_mesh), images streamed through.

    ``pipe``'s params live on submesh A and ``upscaler``'s on submesh B
    (the registry places each per its own mesh). Every image's stage-1+2
    program is dispatched up front (jax async dispatch queues them on A),
    then each result is handed to stage 3 on B as it lands — so image
    i+1's base/SR denoise runs CONCURRENTLY with image i's x4 upscale on
    different chips. Wall-clock approaches max(sum_A, sum_B) + one stage
    latency, vs their sum when the stages share chips. The reference runs
    the three IF stages strictly sequentially on one GPU
    (diffusion_func_if.py:41-65).

    Image i runs as a batch-1 program at ``first_row=i``, so its noise
    keys are ``fold_in(key_for_seed(seed), i)`` — EXACTLY what row i of
    the single-program batched path draws. The same (seed, index) yields
    the same image on any slot topology (the diffusion pipeline's
    per-sample noise-key contract)."""
    n_images = max(1, int(n_images))
    submitted = []
    for i in range(n_images):
        img_dev, _, config = pipe.submit(
            prompt, negative_prompt, steps=steps, sr_steps=sr_steps,
            guidance_scale=guidance_scale, batch=1, seed=seed,
            scheduler=scheduler, first_row=i)
        submitted.append((img_dev, config))

    outs = []
    config = dict(submitted[0][1])
    stages = 2
    for i, (img_dev, _) in enumerate(submitted):
        img_u8 = np.asarray(jax.device_get(img_dev))[:1]
        if upscaler is not None:
            img_u8, stage3 = _run_stage3(
                img_u8, upscaler, prompt, seed,
                final_size or pipe.c.family.sr_size * 4, first_row=i)
            config.update(stage3)
            if "stage3_passes" in stage3:
                stages = 3
        outs.append(img_u8)
    images = np.concatenate(outs, axis=0)
    config["size"] = list(images.shape[1:3])
    config["stages"] = stages
    config["pipeline_parallel"] = 2
    return images, config
