"""Jitted upscalers: latent 2x and text-conditioned pixel 4x.

LatentUpscalePipeline — capability parity with swarm/diffusion/
upscale.py:6-32: the reference runs ``stabilityai/sd-x2-latent-upscaler``
over freshly generated images at 20 steps, guidance 0, with attention
slicing + CPU offload always on. TPU-first redesign: one compiled program
per (batch, size, steps) bucket that does encode -> nearest-2x latent
conditioning -> lax.scan denoise of the 2x latent (UNet sees
concat[noisy_2x, upsampled_low-res], 8 input channels) -> VAE decode.

Upscale4xPipeline — the reference's IF cascade stage 3
(swarm/diffusion/diffusion_func_if.py:31-40 runs
``stabilityai/stable-diffusion-x4-upscaler``): text-conditioned 4x
super-resolution with noise-level conditioning. The UNet denoises 4-ch
latents channel-concatenated with the DDPM-NOISED low-res RGB (7 input
channels), the noise level rides a class-embedding table, and the f=4 VAE
decodes the low-res latent grid straight to 4x pixels. One compiled
program per bucket: encode text -> noise low-res -> scan denoise -> decode.

No offload heuristics in either: bf16 weights + Pallas attention + tiled
decode are always on, and the whole pass stays on-chip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chiaswarm_tpu.core.compile_cache import (
    toplevel_jit,
    GLOBAL_CACHE,
    bucket_batch,
    bucket_image_size,
    static_cache_key,
)
from chiaswarm_tpu.parallel.context import param_mesh_wrap
from chiaswarm_tpu.core.rng import key_for_seed
from chiaswarm_tpu.models.common import upsample2x_nearest
from chiaswarm_tpu.models.vae import AutoencoderKL, tiled_decode
from chiaswarm_tpu.pipelines.components import Components
from chiaswarm_tpu.schedulers import (
    make_noise_schedule,
    make_sampling_schedule,
    resolve,
    sampler_step,
    scale_model_input,
)
from chiaswarm_tpu.schedulers.common import ScheduleConfig
from chiaswarm_tpu.schedulers.sampling import init_sampler_state

DEFAULT_UPSCALE_STEPS = 20  # swarm/diffusion/upscale.py:22-27


class LatentUpscalePipeline:
    """Resident compile-cached 2x upscaler for one Components bundle."""

    def __init__(self, components: Components, attn_impl: str = "auto") -> None:
        self.c = components
        fam = components.family
        if attn_impl not in ("auto", fam.unet.attn_impl):
            import dataclasses

            from chiaswarm_tpu.models.unet import UNet

            components.unet = UNet(
                dataclasses.replace(fam.unet, attn_impl=attn_impl))
        self.schedule_config = ScheduleConfig(
            beta_schedule=fam.beta_schedule,
            prediction_type=fam.prediction_type,
        )
        self.noise_schedule = make_noise_schedule(self.schedule_config)

    def _build_fn(self, *, batch: int, height: int, width: int, steps: int,
                  sampler, tiled: bool):
        fam = self.c.family
        text_encoders = tuple(self.c.text_encoders)
        unet = self.c.unet
        vae = self.c.vae
        f = fam.vae.downscale
        lh, lw = height // f, width // f
        sched = make_sampling_schedule(self.noise_schedule, steps, sampler)
        latent_ch = fam.vae.latent_channels

        def fn(params, ids, row_keys, image):
            seqs = []
            for i, te in enumerate(text_encoders):
                seq, _ = te.apply(params[f"text_encoder_{i}"], ids[i])
                seqs.append(seq)
            ctx = jnp.concatenate(seqs, axis=-1) if len(seqs) > 1 else seqs[0]

            # one key PER batch row (fold_in(key_for_seed(seed), row)):
            # a (seed, row) pair draws the same latents/noise at any
            # batch size and on any slot topology — the per-sample
            # contract shared with pipelines/diffusion.py and the
            # cascade's stage-parallel path
            def stage_keys(stage: int):
                return jax.vmap(
                    lambda k: jax.random.fold_in(k, stage))(row_keys)

            z_lo = jax.vmap(
                lambda img, k: vae.apply(params["vae"], img[None], k,
                                         method=AutoencoderKL.encode)[0]
            )(image, stage_keys(1))                            # (B,lh,lw,C)
            z_cond = upsample2x_nearest(z_lo)                  # (B,2lh,2lw,C)
            noise = jax.vmap(lambda k: jax.random.normal(
                k, (2 * lh, 2 * lw, latent_ch), jnp.float32))(stage_keys(2))
            x = noise * sched.sigmas[0]

            def body(carry, i):
                x, state, rkeys = carry
                inp = scale_model_input(sched, x, i)
                inp = jnp.concatenate([inp, z_cond], axis=-1)  # 8 channels
                t = sched.timesteps[i][None].repeat(batch, axis=0)
                eps = unet.apply(params["unet"], inp, t, ctx)
                both = jax.vmap(jax.random.split)(rkeys)
                rkeys, skeys = both[:, 0], both[:, 1]
                step_noise = jax.vmap(lambda k: jax.random.normal(
                    k, x.shape[1:], jnp.float32))(skeys)
                x, state = sampler_step(sampler, sched, i, x, eps, state,
                                        noise=step_noise, start_index=0)
                return (x, state, rkeys), None

            (x, _, _), _ = jax.lax.scan(
                body, (x, init_sampler_state(x), stage_keys(3)),
                jnp.arange(steps))

            if tiled:
                img = tiled_decode(vae, params["vae"], x)
            else:
                img = vae.apply(params["vae"], x, method=AutoencoderKL.decode)
            # quantize ON DEVICE: uint8 moves 4x fewer bytes over the
            # host link (pipelines/diffusion.py rationale)
            return (jnp.clip((img + 1.0) * 127.5 + 0.5, 0.0, 255.0)
                    ).astype(jnp.uint8)

        return param_mesh_wrap(toplevel_jit(fn), self.c.params)

    def _get_fn(self, **static):
        return GLOBAL_CACHE.cached_executable(
            static_cache_key(id(self.c), "upscale", static),
            lambda: self._build_fn(**static))

    def __call__(self, images: np.ndarray, prompt: str = "",
                 steps: int = DEFAULT_UPSCALE_STEPS, seed: int = 0,
                 scheduler: str | None = None,
                 first_row: int = 0) -> tuple[np.ndarray, dict]:
        """uint8 (B, H, W, 3) -> uint8 (B, 2H, 2W, 3).

        Guidance is 0 by construction (no CFG branch), matching the
        reference's ``guidance_scale=0`` call (upscale.py:22-27).
        ``first_row`` offsets the per-row noise keys so a batch-1 call at
        row i reproduces row i of a batched call (see submit contract in
        pipelines/cascade.py)."""
        fam = self.c.family
        images = np.asarray(images)
        if images.ndim == 3:
            images = images[None]
        in_h, in_w = images.shape[1:3]
        height, width = bucket_image_size(in_h, in_w)
        batch = bucket_batch(images.shape[0])
        sampler = resolve(scheduler, prediction_type=fam.prediction_type)

        fimg = images.astype(np.float32) / 127.5 - 1.0
        if (in_h, in_w) != (height, width):
            from chiaswarm_tpu.pipelines.diffusion import _resize_batch

            fimg = _resize_batch(fimg, height, width)
        if fimg.shape[0] < batch:
            pad = np.repeat(fimg[-1:], batch - fimg.shape[0], axis=0)
            fimg = np.concatenate([fimg, pad], axis=0)

        ids = [tok.encode_batch([prompt] * batch)
               for tok in self.c.tokenizers]
        fn = self._get_fn(batch=batch, height=height, width=width,
                          steps=int(steps), sampler=sampler,
                          tiled=2 * max(height, width) > 1024)
        base_key = key_for_seed(seed)
        row_keys = jax.vmap(
            lambda r: jax.random.fold_in(base_key, r)
        )(jnp.arange(first_row, first_row + batch))
        img = fn(self.c.params, [jnp.asarray(i) for i in ids],
                 row_keys, jnp.asarray(fimg))
        img_u8 = np.asarray(jax.device_get(img))  # uint8 off-chip
        # namespaced keys: this config is merged into the generation job's
        # config by the callers — must not clobber its steps/scheduler
        config = {
            "upscaler": self.c.model_name,
            "scale": 2,
            "upscale_steps": int(steps),
            "upscale_scheduler": sampler.kind,
        }
        return img_u8[: images.shape[0]], config


DEFAULT_X4_STEPS = 75       # StableDiffusionUpscalePipeline default
DEFAULT_X4_GUIDANCE = 9.0   # its guidance_scale default
DEFAULT_NOISE_LEVEL = 20    # its noise_level default


class Upscale4xPipeline:
    """Resident compile-cached SD-x4-upscaler for one Components bundle
    (family kind "upscaler4" — stabilityai/stable-diffusion-x4-upscaler).
    """

    def __init__(self, components: Components, attn_impl: str = "auto") -> None:
        self.c = components
        fam = components.family
        if attn_impl not in ("auto", fam.unet.attn_impl):
            import dataclasses

            from chiaswarm_tpu.models.unet import UNet

            components.unet = UNet(
                dataclasses.replace(fam.unet, attn_impl=attn_impl))
        self.schedule_config = ScheduleConfig(
            beta_schedule=fam.beta_schedule,
            prediction_type=fam.prediction_type,
        )
        self.noise_schedule = make_noise_schedule(self.schedule_config)

    def _build_fn(self, *, batch: int, height: int, width: int, steps: int,
                  sampler, use_cfg: bool, noise_level: int, tiled: bool):
        from chiaswarm_tpu.schedulers.common import add_noise

        fam = self.c.family
        text_encoders = tuple(self.c.text_encoders)
        unet = self.c.unet
        vae = self.c.vae
        sched = make_sampling_schedule(self.noise_schedule, steps, sampler)
        latent_ch = fam.vae.latent_channels
        noise_sched = self.noise_schedule

        def encode(params, ids):
            seqs = []
            for i, te in enumerate(text_encoders):
                seq, _ = te.apply(params[f"text_encoder_{i}"], ids[i])
                seqs.append(seq)
            return (jnp.concatenate(seqs, axis=-1) if len(seqs) > 1
                    else seqs[0])

        def fn(params, ids, neg_ids, row_keys, image, guidance):
            ctx = encode(params, ids)
            if use_cfg:
                ctx = jnp.concatenate([encode(params, neg_ids), ctx], axis=0)

            # per-row keys: the (seed, row) contract shared with the
            # other pipelines (see LatentUpscalePipeline above)
            def stage_keys(stage: int):
                return jax.vmap(
                    lambda k: jax.random.fold_in(k, stage))(row_keys)

            # DDPM-noise the low-res conditioning image at noise_level —
            # the forward process q(x_t | x_0) on the model's own schedule
            # (StableDiffusionUpscalePipeline's low_res_scheduler step)
            level = jnp.full((batch,), noise_level, jnp.int32)
            img_noised = add_noise(
                noise_sched, image,
                jax.vmap(lambda k, shp=image.shape[1:]: jax.random.normal(
                    k, shp, jnp.float32))(stage_keys(1)), level)

            x = jax.vmap(lambda k: jax.random.normal(
                k, (height, width, latent_ch), jnp.float32))(stage_keys(2))
            x = x * sched.sigmas[0]
            labels = (jnp.concatenate([level, level], axis=0)
                      if use_cfg else level)

            def body(carry, i):
                x, state, rkeys = carry
                inp = scale_model_input(sched, x, i)
                inp = jnp.concatenate([inp, img_noised], axis=-1)  # 7 ch
                if use_cfg:
                    inp = jnp.concatenate([inp, inp], axis=0)
                t = sched.timesteps[i][None].repeat(inp.shape[0], axis=0)
                out = unet.apply(params["unet"], inp, t, ctx,
                                 class_labels=labels)
                if use_cfg:
                    out_u, out_c = jnp.split(out, 2, axis=0)
                    out = out_u + guidance * (out_c - out_u)
                both = jax.vmap(jax.random.split)(rkeys)
                rkeys, skeys = both[:, 0], both[:, 1]
                step_noise = jax.vmap(lambda k: jax.random.normal(
                    k, x.shape[1:], jnp.float32))(skeys)
                x, state = sampler_step(sampler, sched, i, x, out, state,
                                        noise=step_noise, start_index=0)
                return (x, state, rkeys), None

            (x, _, _), _ = jax.lax.scan(
                body, (x, init_sampler_state(x), stage_keys(3)),
                jnp.arange(steps))

            if tiled:
                img = tiled_decode(vae, params["vae"], x)
            else:
                img = vae.apply(params["vae"], x, method=AutoencoderKL.decode)
            # quantize ON DEVICE (pipelines/diffusion.py rationale)
            return (jnp.clip((img + 1.0) * 127.5 + 0.5, 0.0, 255.0)
                    ).astype(jnp.uint8)

        return param_mesh_wrap(toplevel_jit(fn), self.c.params)

    def _get_fn(self, **static):
        return GLOBAL_CACHE.cached_executable(
            static_cache_key(id(self.c), "upscale4", static),
            lambda: self._build_fn(**static))

    def __call__(self, images: np.ndarray, prompt: str = "",
                 negative_prompt: str = "",
                 steps: int = DEFAULT_X4_STEPS,
                 guidance_scale: float = DEFAULT_X4_GUIDANCE,
                 noise_level: int = DEFAULT_NOISE_LEVEL,
                 seed: int = 0,
                 scheduler: str | None = None,
                 first_row: int = 0) -> tuple[np.ndarray, dict]:
        """uint8 (B, H, W, 3) -> uint8 (B, 4H, 4W, 3).

        The latent grid runs at the LOW-RES spatial size (the f=4 VAE does
        the 4x), so a 256px input costs a 256-grid denoise — cheaper per
        output pixel than the x2 latent upscaler's 2x-grid scan."""
        fam = self.c.family
        images = np.asarray(images)
        if images.ndim == 3:
            images = images[None]
        in_h, in_w = images.shape[1:3]
        height, width = bucket_image_size(in_h, in_w)
        batch = bucket_batch(images.shape[0])
        sampler = resolve(scheduler, prediction_type=fam.prediction_type)
        use_cfg = float(guidance_scale) > 1.0

        fimg = images.astype(np.float32) / 127.5 - 1.0
        if (in_h, in_w) != (height, width):
            from chiaswarm_tpu.pipelines.diffusion import _resize_batch

            fimg = _resize_batch(fimg, height, width)
        if fimg.shape[0] < batch:
            pad = np.repeat(fimg[-1:], batch - fimg.shape[0], axis=0)
            fimg = np.concatenate([fimg, pad], axis=0)

        ids = [tok.encode_batch([prompt] * batch)
               for tok in self.c.tokenizers]
        neg = [tok.encode_batch([negative_prompt or ""] * batch)
               for tok in self.c.tokenizers]
        fn = self._get_fn(batch=batch, height=height, width=width,
                          steps=int(steps), sampler=sampler,
                          use_cfg=use_cfg, noise_level=int(noise_level),
                          tiled=4 * max(height, width) > 1024)
        base_key = key_for_seed(seed)
        row_keys = jax.vmap(
            lambda r: jax.random.fold_in(base_key, r)
        )(jnp.arange(first_row, first_row + batch))
        img = fn(self.c.params, [jnp.asarray(i) for i in ids],
                 [jnp.asarray(i) for i in neg], row_keys,
                 jnp.asarray(fimg), jnp.float32(guidance_scale))
        img_u8 = np.asarray(jax.device_get(img))  # uint8 off-chip
        config = {
            "upscaler": self.c.model_name,
            "scale": 4,
            "upscale_steps": int(steps),
            "upscale_noise_level": int(noise_level),
            "upscale_scheduler": sampler.kind,
        }
        return img_u8[: images.shape[0]], config
