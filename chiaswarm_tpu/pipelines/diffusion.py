"""The unified diffusion pipeline: txt2img / img2img / inpaint in ONE jitted
program.

The reference runs four diffusers pipeline classes for these modes, chosen by
server-sent class names (swarm/job_arguments.py:104-151) and executed at
swarm/diffusion/diffusion_func.py:96. TPU-first redesign: one compiled
executable per (family, batch, size, steps, mode) bucket containing the whole
flow — text encode -> (optional) init-latent prep -> lax.scan denoise loop
with classifier-free guidance -> VAE decode. No host round-trips inside; the
only host work is tokenization and uint8 conversion.

Modes fold into static booleans:
- txt2img: no init latents (pure noise at sigma_max)
- img2img: init latents + noise at sigma[start] (strength -> start index,
  mirroring the reference's strength semantics)
- inpaint: img2img + per-step known-region re-projection (model-agnostic
  "legacy" inpainting; 9-channel inpaint checkpoints plug in via family
  config sample_channels)

Guidance scale rides as a *traced* scalar so changing it never recompiles.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Iterable

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
from chiaswarm_tpu.obs import numerics as _numerics
from chiaswarm_tpu.obs import trace as obs_trace
from chiaswarm_tpu.obs.trace import span
from chiaswarm_tpu.parallel.context import param_mesh_wrap
from chiaswarm_tpu.convert.quantize import (
    dequantize_tree,
    fake_quant_activation,
)
from chiaswarm_tpu.core.rng import key_for_seed
from chiaswarm_tpu.models.vae import AutoencoderKL
from chiaswarm_tpu.pipelines.components import Components
from chiaswarm_tpu.schedulers import (
    SamplerConfig,
    SamplingSchedule,
    make_noise_schedule,
    make_sampling_schedule,
    reproject_known,
    reproject_known_rows,
    resolve,
    sampler_step,
    sampler_step_rows,
    scale_model_input,
    scale_model_input_rows,
)
from chiaswarm_tpu.obs.metrics import (
    STEPPER_UNET_EVAL_MODES,
    steps_skipped_counter,
    unet_evals_counter,
    unet_evals_per_image_histogram,
)
from chiaswarm_tpu.schedulers.common import ScheduleConfig
from chiaswarm_tpu.schedulers.sampling import SamplerState, init_sampler_state

# ---- step collapse: DeepCache feature reuse (ISSUE 12) -----------------
#
# The denoise loop's dominant cost is steps x full-UNet. DeepCache (Ma
# et al. 2023) observes that the DEEP UNet features change slowly across
# adjacent steps: on designated steps the deep blocks are skipped and
# their cached activation is replayed, only the shallow level-0 blocks
# recompute (models/unet.py documents the seam). Master switch is
# ``CHIASWARM_DEEPCACHE``; the schedule itself is PER JOB
# (``GenerateRequest.reuse_schedule`` / the job's ``reuse_schedule``
# parameter) and rides as a TRACED table, so changing it never
# recompiles — the executable is keyed only by the static ``reuse``
# flag, and with the env off the lowered program is byte-identical to
# the pre-reuse build (the PR-11 taps-off gate pattern).

ENV_DEEPCACHE = "CHIASWARM_DEEPCACHE"

#: step-collapse observability (obs/metrics.py, ISSUE 12): per-row UNet
#: evaluations by mode, deep-blocks-skipped steps, and the per-image
#: full-eval histogram — pre-seeded so dashboards see zeroes from the
#: first scrape (the ISSUE-6 convention)
_UNET_EVALS = unet_evals_counter()
_STEPS_SKIPPED = steps_skipped_counter()
_EVALS_PER_IMAGE = unet_evals_per_image_histogram()
for _mode in STEPPER_UNET_EVAL_MODES:
    _UNET_EVALS.inc(0, mode=_mode)
_STEPS_SKIPPED.inc(0)


def deepcache_enabled() -> bool:
    """DeepCache feature reuse is OPT-IN (quality-gated like int8
    weights, ISSUE 8): with the env unset/off every per-job
    ``reuse_schedule`` is ignored and the compiled programs are the
    pre-reuse builds bit for bit."""
    return os.environ.get(ENV_DEEPCACHE, "").strip().lower() in (
        "1", "true", "on", "yes")


def normalize_reuse_schedule(steps: int, schedule: Iterable[int] | str,
                             start_step: int = 0) -> tuple[int, ...]:
    """Canonicalize a per-job DeepCache reuse schedule.

    Accepts an iterable of ladder indices (the steps whose deep blocks
    replay the cache) or the compact cadence form ``"every:N"`` —
    refresh the cache every Nth executed step, reuse the rest (N=3
    skips 2 of every 3 deep passes). Indices must lie strictly inside
    ``(start_step, steps)``: the first executed step has no cache to
    reuse, and out-of-range indices are a caller error, not a silent
    no-op. Returns a sorted, deduplicated tuple — the canonical form
    checkpoints record and resume validation compares
    (serving/stepper.py::_validate_resume)."""
    if isinstance(schedule, str):
        text = schedule.strip().lower()
        if not text.startswith("every:"):
            raise ValueError(
                f"reuse_schedule string must be 'every:N', got "
                f"{schedule!r}")
        try:
            cadence = int(text.split(":", 1)[1])
        except ValueError as exc:
            raise ValueError(
                f"reuse_schedule cadence in {schedule!r} is not an "
                f"integer") from exc
        if cadence < 2:
            raise ValueError("reuse cadence must be >= 2 (1 would never "
                             "refresh the cache)")
        schedule = [i for i in range(start_step + 1, steps)
                    if (i - start_step) % cadence != 0]
    try:
        out = sorted({int(i) for i in schedule})
    except (TypeError, ValueError) as exc:
        # a bare int / None entries must stay a ValueError: the lane
        # path converts ValueError to LaneReject and the solo path's
        # canonical user error is classified fatal-bad-request — a
        # TypeError here would escape into the breaker taxonomy and
        # let K malformed requests quarantine a healthy model
        raise ValueError(
            f"reuse_schedule must be 'every:N' or an iterable of "
            f"ladder indices, got {schedule!r}") from exc
    for i in out:
        if not start_step < i < steps:
            raise ValueError(
                f"reuse step {i} outside the executed ladder "
                f"({start_step}, {steps}) — the first executed step "
                f"must run the full UNet to fill the cache")
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class GenerateRequest:
    """One generation request (pre-normalized by the node dispatcher).

    ``prompt``/``negative_prompt`` may be a tuple of per-ROW prompts
    (length == ``batch``) — the coalesced-jobs path rides different
    hive jobs on one batched program (node/executor.py). When
    ``sample_seed_rows`` is set, row b's noise key is
    ``fold_in(key_for_seed(seed_b), row_b)`` — exactly what that row
    would get in its own solo job — instead of deriving every row from
    ``seed``.
    """

    prompt: str | tuple[str, ...]
    negative_prompt: str | tuple[str, ...] = ""
    steps: int = 30
    guidance_scale: float = 7.5
    height: int = 512
    width: int = 512
    batch: int = 1
    seed: int = 0
    scheduler: str | None = None  # diffusers class name from the hive
    # per-row (seed, row-index) pairs, length == batch (coalesced jobs)
    sample_seed_rows: tuple[tuple[int, int], ...] | None = None
    # explicit standard-normal initial noise (B|1, H/f, W/f, C): replaces
    # the per-row drawn noise so a fixed-latent render can be compared
    # image-for-image against an external reference (diffusers golden,
    # tests/test_real_checkpoint.py). Deterministic samplers (DDIM/DPM)
    # then walk the exact same trajectory.
    init_noise: np.ndarray | None = None
    # img2img / inpaint
    init_image: np.ndarray | None = None   # (H, W, 3) uint8 or float [-1,1]
    strength: float = 0.8
    mask: np.ndarray | None = None         # (H, W) float, 1 = regenerate
    # coalesced img2img/inpaint: ``init_image`` is a per-JOB (J, H, W, 3)
    # stack (``mask`` a per-JOB (J, H, W) stack) and init_groups[j] =
    # (encode_seed, n_rows) — job j's image is VAE-encoded with ITS OWN
    # seed through the same batch-1 executable its solo run uses (bitwise
    # solo equality by construction), then repeated over its rows
    init_groups: tuple[tuple[int, int], ...] | None = None
    tiled_decode: bool = False
    # ControlNet (swarm/diffusion/diffusion_func.py:29-39)
    controlnet: Any = None                 # ControlNetBundle
    control_image: np.ndarray | None = None  # (H, W, 3) conditioning image
    control_scale: float = 1.0             # traced; never recompiles
    # instruct-pix2pix dual guidance (image_conditioned families)
    image_guidance_scale: float = 1.5      # traced; never recompiles
    # DeepCache step-level feature reuse (ISSUE 12): ladder indices
    # whose deep UNet blocks replay the cached activation, or the
    # "every:N" cadence form — see normalize_reuse_schedule. Ignored
    # unless CHIASWARM_DEEPCACHE is on; rides as a traced table, so
    # per-job schedules never recompile.
    reuse_schedule: tuple[int, ...] | str | None = None


def _make_text_encode(text_encoders):
    """Trace-time text-encode over a tuple of encoder modules — shared by
    the solo generate program and the step scheduler's context-encode
    executable so both produce identical embeddings for a row."""
    def encode_text(params, ids_list):
        seqs, pooled = [], None
        for i, te in enumerate(text_encoders):
            seq, pool = te.apply(params[f"text_encoder_{i}"], ids_list[i])
            seqs.append(seq)
            pooled = pool  # SDXL: pooled comes from the last encoder
        return (jnp.concatenate(seqs, axis=-1)
                if len(seqs) > 1 else seqs[0]), pooled

    return encode_text


def _params_mesh(params):
    """The dp x tp mesh the params are sharded over, or None (single-chip
    or unsharded)."""
    from jax.sharding import NamedSharding

    for leaf in jax.tree.leaves(params):
        s = getattr(leaf, "sharding", None)
        if isinstance(s, NamedSharding) and "data" in s.mesh.shape \
                and s.mesh.devices.size > 1:
            return s.mesh
    return None


def img2img_start_index(steps: int, strength: float) -> int:
    """img2img strength -> denoise start index, the ONE quantization
    (clip to [0.05, 1], round, never past the last step). Shared by the
    solo program (below), the lane scheduler (serving/stepper.py) and
    the ticket's observable ``denoise_steps`` (workloads/diffusion.py)
    — resume validation keys on this value, so a drift between call
    sites would force spurious clean restarts."""
    strength = float(np.clip(strength, 0.05, 1.0))
    return min(int(round(steps * (1.0 - strength))), steps - 1)


def latent_mask(mask: np.ndarray, lh: int, lw: int,
                downscale: int) -> np.ndarray:
    """Arbitrary-size inpaint mask -> binarized (lh, lw) latent-grid mask
    (1 = regenerate). Shared by the solo generate program's prep and the
    lane admission path (serving/stepper.py) so an inpaint row's mask
    quantization is identical wherever the job runs."""
    mask = np.asarray(mask, dtype=np.float32)
    if mask.shape != (lh, lw):
        if mask.shape != (lh * downscale, lw * downscale):
            # bring arbitrary mask sizes onto the bucketed pixel grid
            from PIL import Image

            mask = np.asarray(Image.fromarray(
                (mask * 255).clip(0, 255).astype(np.uint8)
            ).resize((lw * downscale, lh * downscale), Image.NEAREST),
                dtype=np.float32) / 255.0
        # downsample to the latent grid by box-averaging
        mask = mask.reshape(lh, downscale, lw, downscale).mean((1, 3))
    return (mask > 0.5).astype(np.float32)


def _to_float_image(img: np.ndarray) -> np.ndarray:
    img = np.asarray(img)
    if img.dtype == np.uint8:
        img = img.astype(np.float32) / 127.5 - 1.0
    return img.astype(np.float32)


def _resize_batch(img: np.ndarray, height: int, width: int) -> np.ndarray:
    """Host-side LANCZOS resize onto the bucketed grid (uint8 or float)."""
    from PIL import Image

    single = img.ndim == 3
    frames = img[None] if single else img
    as_u8 = frames.dtype == np.uint8
    out = []
    for frame in frames:
        if not as_u8:
            frame = ((frame + 1.0) * 127.5).clip(0, 255).astype(np.uint8)
        resized = np.asarray(Image.fromarray(frame).resize(
            (width, height), Image.LANCZOS))
        out.append(resized if as_u8 else
                   resized.astype(np.float32) / 127.5 - 1.0)
    stacked = np.stack(out)
    return stacked[0] if single else stacked


@dataclasses.dataclass
class PendingImages:
    """A dispatched (possibly still-executing) generate program's uint8
    output. ``wait()`` blocks on the device->host transfer and un-buckets
    back to the exact requested size."""

    device_images: Any
    compiled_hw: tuple[int, int]
    requested_hw: tuple[int, int]
    requested_batch: int

    def wait(self) -> np.ndarray:
        # the "decode" span. SOLO: under async dispatch the denoise +
        # VAE decode + device->host transfer all settle HERE, so this is
        # where the chip time shows in the trace. LANE: the driver has
        # already waited the decode out before it resolved the job's
        # future (Lane._flush_handoff), so the span times the FETCH of a
        # finished uint8 array and the un-bucket crop only — the decode's
        # device time is the job's ``lane.handoff`` span
        with span("decode", batch=self.requested_batch):
            return self._wait()

    def _wait(self) -> np.ndarray:
        img_u8 = np.asarray(jax.device_get(self.device_images))
        height, width = self.compiled_hw
        req_h, req_w = self.requested_hw
        # un-bucket: scale-to-cover + center-crop back to the exact request
        # (plain resize would stretch when the bucket changed aspect ratio)
        if (height, width) != (req_h, req_w):
            from PIL import Image

            scale = max(req_h / height, req_w / width)
            rh, rw = (max(req_h, round(height * scale)),
                      max(req_w, round(width * scale)))
            y0, x0 = (rh - req_h) // 2, (rw - req_w) // 2
            img_u8 = np.stack([
                np.asarray(Image.fromarray(frame).resize(
                    (rw, rh), Image.LANCZOS))[y0:y0 + req_h, x0:x0 + req_w]
                for frame in img_u8
            ])
        return img_u8[: self.requested_batch]


class DiffusionPipeline:
    """Resident, compile-cached executor for one Components bundle."""

    def __init__(self, components: Components, attn_impl: str = "auto") -> None:
        self.c = components
        if attn_impl != components.unet.config.attn_impl and attn_impl != "auto":
            # modules are cheap static descriptions: rebuild the UNet with
            # the forced attention dispatch (param tree is unchanged)
            from chiaswarm_tpu.models.unet import UNet

            components.unet = UNet(
                dataclasses.replace(components.family.unet,
                                    attn_impl=attn_impl)
            )
        fam = components.family
        self.schedule_config = ScheduleConfig(
            beta_schedule=fam.beta_schedule,
            prediction_type=fam.prediction_type,
        )
        self.noise_schedule = make_noise_schedule(self.schedule_config)

    # ---------- host-side helpers ----------

    def _tokenize(self, prompts: list[str]) -> list[np.ndarray]:
        return [tok.encode_batch(prompts) for tok in self.c.tokenizers]

    def _latent_hw(self, height: int, width: int) -> tuple[int, int]:
        f = self.c.family.vae.downscale
        return height // f, width // f

    # ---------- jitted core ----------

    def _build_fn(self, *, batch: int, height: int, width: int, steps: int,
                  start_step: int, sampler: SamplerConfig, use_cfg: bool,
                  has_init: bool, has_mask: bool, tiled: bool,
                  has_control: bool = False, has_noise: bool = False,
                  reuse: bool = False):
        # capture only the static module descriptions — NOT the Components
        # bundle, whose .params would otherwise stay pinned by the
        # executable-cache closure after the param LRU evicts them
        fam = self.c.family
        text_encoders = tuple(self.c.text_encoders)
        unet = self.c.unet
        vae = self.c.vae
        lh, lw = self._latent_hw(height, width)
        sched = make_sampling_schedule(self.noise_schedule, steps, sampler)
        needs_xl = fam.unet.addition_embed_dim is not None

        control_net = control_embed = None
        if has_control:
            from chiaswarm_tpu.models.controlnet import (
                ControlCondEmbedding,
                ControlNet,
            )

            control_net = ControlNet(fam.unet)
            control_embed = ControlCondEmbedding(
                fam.unet.block_out_channels[0],
                downscale=fam.vae.downscale)

        encode_text = _make_text_encode(text_encoders)

        pix2pix = fam.image_conditioned
        if reuse and (pix2pix or has_control):
            # dual-CFG conditioning and the ControlNet trunk both feed
            # the deep blocks per step — skipping those blocks while
            # still paying their conditioning is incoherent; submit()
            # never requests this combination
            raise ValueError("DeepCache reuse supports the plain "
                             "txt2img/img2img/inpaint programs only")

        # every jitted program is a local def NAMED for its cache-key tag
        # (generate / stepper_encode / stepper_init / stepper_step /
        # stepper_ctrl_embed / stepper_decode): jax calls the HLO module
        # jit_<name>, so a device trace splits its time by program
        def generate(params, ids, neg_ids, sample_keys, guidance,
                     init_latent, mask, control_params, control_cond,
                     control_scale, image_guidance, noise_override,
                     reuse_tab=None):
            # int8 weight residency (convert/quantize.py): dequantize AT
            # USE, inside the traced program — HBM holds the int8 codes,
            # XLA fuses the casts into the consumers. No-op on fp trees.
            params = dequantize_tree(params)
            control_params = dequantize_tree(control_params)
            ctx, pooled = encode_text(params, ids)
            # swarmlens probes (ISSUE 11): identity unless the probe is
            # enabled via CHIASWARM_NUMERICS at trace time — the cache
            # key carries the tap fingerprint, so flipping the env can
            # never serve a tapped program from a taps-off slot
            ctx = _numerics.tap("diffusion.text_ctx", ctx)
            if pix2pix:
                # dual CFG rides a tripled batch: [uncond, image-only,
                # text+image] (timbrooks/instruct-pix2pix semantics; the
                # reference reaches it via the diffusers pipeline class)
                nctx, _ = encode_text(params, neg_ids)
                ctx = jnp.concatenate([nctx, nctx, ctx], axis=0)
            elif use_cfg:
                nctx, npooled = encode_text(params, neg_ids)
                ctx = jnp.concatenate([nctx, ctx], axis=0)
                if pooled is not None:
                    pooled = jnp.concatenate([npooled, pooled], axis=0)

            ctx = fake_quant_activation(ctx, tag="unet.ctx")

            added = None
            if needs_xl:
                time_ids = jnp.asarray(
                    [height, width, 0, 0, height, width], jnp.float32
                )[None, :].repeat(ctx.shape[0], axis=0)
                added = {"time_ids": time_ids,
                         "text_embeds": pooled[:, : fam.unet.addition_pooled_dim]}

            # per-SAMPLE noise streams: row b's noise depends only on its
            # own key, so image b is identical whether generated at
            # batch=1 or inside a larger batch (seed reproducibility is
            # batch-size-invariant — and the precondition for ever
            # coalescing different jobs into one batched program)
            def draw(keys):
                return jax.vmap(lambda k: jax.random.normal(
                    k, (lh, lw, fam.vae.latent_channels), jnp.float32)
                )(keys)

            both = jax.vmap(jax.random.split)(sample_keys)  # (B, 2, key)
            sample_keys, nkeys = both[:, 0], both[:, 1]
            noise = noise_override if has_noise else draw(nkeys)
            sigma_start = sched.sigmas[start_step]
            if pix2pix:
                # image latents condition via channel-concat (UNSCALED, the
                # pix2pix convention); generation starts from pure noise
                img_cond = init_latent / fam.vae.scaling_factor
                x = noise * sched.sigmas[0]
            elif has_init:
                x = init_latent + noise * sigma_start
            else:
                x = noise * sigma_start

            if has_mask:
                known = init_latent  # clean latents of the source image

            cond_emb = None
            if has_control:
                # hint embedding is timestep-independent: evaluate ONCE
                # here, outside the scan (diffusers recomputes per step)
                cond_emb = control_embed.apply(
                    control_params["embed"], control_cond)
                cond_emb = jnp.repeat(cond_emb, batch, axis=0)
                if use_cfg:
                    cond_emb = jnp.concatenate([cond_emb, cond_emb], axis=0)

            if reuse:
                # DeepCache carry: the deep activation for the (CFG-
                # expanded) batch + a validity flag. Both branches of the
                # lax.cond are compiled ONCE — the per-step reuse_tab
                # lookup selects at run time, so any schedule rides the
                # same executable and only the taken branch executes.
                cache0 = jnp.zeros(
                    ((2 * batch if use_cfg else batch), lh, lw,
                     fam.unet.block_out_channels[1]), unet.dtype)

                def unet_reuse_eval(inp_b, t_b, ctx_b, added_b, cache, ok,
                                    i):
                    reuse_now = jnp.logical_and(reuse_tab[i], ok)

                    def shallow(ops):
                        inp_b, t_b, cache = ops
                        out = unet.apply(params["unet"], inp_b, t_b,
                                         ctx_b, added_b,
                                         cached_deep=cache)
                        return out, cache

                    def full(ops):
                        inp_b, t_b, _cache = ops
                        return unet.apply(params["unet"], inp_b, t_b,
                                          ctx_b, added_b,
                                          return_deep=True)

                    out, cache = jax.lax.cond(reuse_now, shallow, full,
                                              (inp_b, t_b, cache))
                    return out, cache, jnp.ones((), bool)

            def body(carry, idx):
                if reuse:
                    x, state, carry_keys, cache, cache_ok = carry
                else:
                    x, state, carry_keys = carry
                i = idx + start_step
                inp = scale_model_input(sched, x, i)
                # low-precision activations (CHIASWARM_ACTIVATIONS,
                # default off = identity): the UNet block input for this
                # step — every branch below (pix2pix triple, CFG double,
                # solo) derives its batch from this tensor, so one seam
                # covers them all; the text context is quantized once
                # outside the scan
                inp = fake_quant_activation(inp, tag="unet.in")
                if pix2pix:
                    inp3 = jnp.concatenate([inp, inp, inp], axis=0)
                    img3 = jnp.concatenate(
                        [jnp.zeros_like(img_cond), img_cond, img_cond],
                        axis=0)
                    t3 = sched.timesteps[i][None].repeat(3 * batch, axis=0)
                    out = unet.apply(params["unet"],
                                     jnp.concatenate([inp3, img3], axis=-1),
                                     t3, ctx, added)
                    e_unc, e_img, e_full = jnp.split(out, 3, axis=0)
                    eps = (e_unc + image_guidance * (e_img - e_unc)
                           + guidance * (e_full - e_img))
                elif use_cfg:
                    inp2 = jnp.concatenate([inp, inp], axis=0)
                    t2 = sched.timesteps[i][None].repeat(2 * batch, axis=0)
                    down_res = mid_res = None
                    if has_control:
                        down_res, mid_res = control_net.apply(
                            control_params["net"], inp2, t2, ctx, cond_emb,
                            added, control_scale)
                    if reuse:
                        out, cache, cache_ok = unet_reuse_eval(
                            inp2, t2, ctx, added, cache, cache_ok, i)
                    else:
                        out = unet.apply(params["unet"], inp2, t2, ctx,
                                         added, down_res, mid_res)
                    eps_u, eps_c = jnp.split(out, 2, axis=0)
                    eps = eps_u + guidance * (eps_c - eps_u)
                else:
                    t1 = sched.timesteps[i][None].repeat(batch, axis=0)
                    down_res = mid_res = None
                    if has_control:
                        down_res, mid_res = control_net.apply(
                            control_params["net"], inp, t1, ctx, cond_emb,
                            added, control_scale)
                    if reuse:
                        eps, cache, cache_ok = unet_reuse_eval(
                            inp, t1, ctx, added, cache, cache_ok, i)
                    else:
                        eps = unet.apply(params["unet"], inp, t1, ctx,
                                         added, down_res, mid_res)
                eps = _numerics.tap("diffusion.eps", eps, step=i)
                keys, skeys = jax.vmap(
                    lambda k: tuple(jax.random.split(k)))(carry_keys)
                step_noise = draw(skeys)
                x, state = sampler_step(sampler, sched, i, x, eps, state,
                                        noise=step_noise,
                                        start_index=start_step)
                if has_mask:
                    # re-project known region onto the next noise level
                    keys, mkeys = jax.vmap(
                        lambda k: tuple(jax.random.split(k)))(keys)
                    renoise = draw(mkeys)
                    x = reproject_known(sched, i, x, known, mask, renoise)
                # the scheduler carry: the value the next step consumes
                x = _numerics.tap("diffusion.latents", x, step=i)
                if reuse:
                    return (x, state, keys, cache, cache_ok), None
                return (x, state, keys), None

            n_steps = steps - start_step
            carry0 = ((x, init_sampler_state(x), sample_keys, cache0,
                       jnp.zeros((), bool)) if reuse
                      else (x, init_sampler_state(x), sample_keys))
            carry_out, _ = jax.lax.scan(body, carry0, jnp.arange(n_steps))
            x = carry_out[0]
            x = _numerics.tap("diffusion.final_latents", x)

            if tiled:
                from chiaswarm_tpu.models.vae import tiled_decode

                img = tiled_decode(vae, params["vae"], x)
            else:
                img = vae.apply(params["vae"], x,
                                method=AutoencoderKL.decode)
            # quantize ON DEVICE: the host link moves 4x fewer bytes as
            # uint8 than as fp32
            return _numerics.tap(
                "diffusion.image_u8",
                (jnp.clip((img + 1.0) * 127.5 + 0.5, 0.0, 255.0)
                 ).astype(jnp.uint8))

        # seq>1 param meshes trace under the sequence-parallel context so
        # ops.attention routes the large spatial self-attentions through
        # the ppermute ring (parallel/ring_attention.py)
        return param_mesh_wrap(toplevel_jit(generate), self.c.params)

    def _get_fn(self, **static: Any):
        return GLOBAL_CACHE.cached_executable(
            static_cache_key(id(self.c), "generate", static),
            lambda: self._build_fn(**static)
        )

    # ---------- public API ----------

    def encode_init_image(self, image: np.ndarray, height: int, width: int,
                          seed: int) -> jnp.ndarray:
        """Host image(s) -> scaled latents (the img2img/inpaint init).

        Accepts (H, W, 3) for one shared init or (B, H, W, 3) for per-item
        inits (video frames riding the batch axis, workloads/video.py).

        COMPILED: an eager ``vae.apply`` dispatches hundreds of tiny ops
        per call, each a host round trip. The executable rides the
        global LRU like every other program (thread-safe, evictable) and
        the batch is padded to the pow2 compile bucket so per-frame-count
        vid2vid chunks cannot fan out executables; the module closure
        carries no params (they pass as an argument, so the param LRU
        can still evict the tree)."""
        img = _to_float_image(image)
        if img.ndim == 3:
            img = img[None]
        if img.shape[1:3] != (height, width):
            raise ValueError(
                f"init image {img.shape[1:3]} != requested {(height, width)}; "
                "resize on host first (node.job_args does this)"
            )
        n = img.shape[0]
        bucket = bucket_batch(n)
        if n < bucket:
            img = np.concatenate(
                [img, np.repeat(img[-1:], bucket - n, axis=0)], axis=0)
        vae = self.c.vae
        fn = GLOBAL_CACHE.cached_executable(
            static_cache_key(id(self.c), "encode",
                             {"batch": bucket, "height": height,
                              "width": width}),
            lambda: toplevel_jit(
                lambda params, x, key: vae.apply(
                    dequantize_tree(params), x, key,
                    method=AutoencoderKL.encode)))
        z = fn(self.c.params["vae"], jnp.asarray(img), key_for_seed(seed))
        return z[:n]

    # ---------- step-scheduler executables (serving/stepper.py) ----------
    #
    # Continuous step-level batching decomposes the solo generate program
    # into four resident executables per lane bucket: context encode, row
    # init (initial noise draw), ONE denoise step over the whole lane
    # (per-row timesteps/sigmas — rows at different progress coexist),
    # and VAE decode for retiring rows. All four ride the global
    # executable LRU, so admitting a row never compiles anything: the
    # lane-program count is bounded by the (batch, size, steps-capacity,
    # sampler) buckets alone.

    def stepper_encode_fn(self, *, batch: int):
        """(params, ids, neg_ids) -> (ctx_u, ctx_c, pooled_u, pooled_c)
        for ``batch`` rows — the admission-time text encode. Same
        per-row math as the solo program's in-trace encode."""
        text_encoders = tuple(self.c.text_encoders)

        def build():
            encode_text = _make_text_encode(text_encoders)

            def stepper_encode(params, ids, neg_ids):
                params = dequantize_tree(params)
                ctx_c, pooled_c = encode_text(params, ids)
                ctx_u, pooled_u = encode_text(params, neg_ids)
                return ctx_u, ctx_c, pooled_u, pooled_c

            return param_mesh_wrap(toplevel_jit(stepper_encode),
                                   self.c.params)

        return GLOBAL_CACHE.cached_executable(
            static_cache_key(id(self.c), "stepper_encode",
                             {"batch": batch}), build)

    def stepper_row_init_fn(self, *, batch: int, height: int, width: int):
        """(sample_keys, sigma0) -> (carry_keys, x0): the initial split +
        noise draw for freshly admitted rows. Identical to the solo
        program's prologue (split, draw, scale by sigma[start]), so a
        spliced row starts on exactly its solo trajectory."""
        fam = self.c.family
        lh, lw = self._latent_hw(height, width)

        def build():
            def stepper_init(sample_keys, sigma0):
                both = jax.vmap(jax.random.split)(sample_keys)
                carry, nkeys = both[:, 0], both[:, 1]
                noise = jax.vmap(lambda k: jax.random.normal(
                    k, (lh, lw, fam.vae.latent_channels), jnp.float32)
                )(nkeys)
                return carry, noise * sigma0.reshape(-1, 1, 1, 1)

            return toplevel_jit(stepper_init)

        return GLOBAL_CACHE.cached_executable(
            static_cache_key(id(self.c), "stepper_init",
                             {"batch": batch, "height": height,
                              "width": width}), build)

    def stepper_step_fn(self, *, batch: int, height: int, width: int,
                        steps_cap: int, sampler: SamplerConfig,
                        has_control: bool = False, reuse: bool = False):
        """ONE denoise step over a full lane of ``batch`` rows.

        Per-row traced state: latents, carry keys, step index, start
        index, sigma/timestep tables (each row owns its ladder, padded to
        ``steps_cap``), guidance scale, multistep history, active mask —
        and, since ISSUE 7, the image-mode row state: ``known`` (clean
        source latents), ``mask`` (latent-grid inpaint mask) and
        ``mask_on`` (per-row flag selecting the inpaint re-projection).
        Inpaint math is always compiled in and selected per ROW: rows
        without a mask keep the txt2img/img2img carry-key trajectory
        bit-for-bit (the second key split is computed but discarded), so
        txt2img, img2img (nonzero per-row start index) and inpaint rows
        share one lane program. Inactive (padding / retired) rows
        compute and are discarded by the active mask — their carries
        freeze, so a row admitted into their slot later starts clean.
        Classifier-free guidance is always compiled in; per-row guidance
        rides as a traced vector.

        ``has_control`` compiles the ControlNet branch in: the lane then
        additionally takes the bundle's params, a per-row pre-embedded
        hint stack (``stepper_control_embed_fn``) and a per-row
        conditioning-scale vector. Control lanes are keyed by bundle
        (serving/stepper.py), so every row shares the branch params
        while conditioning images/scales stay per row.

        ``reuse`` compiles the DeepCache branch in (ISSUE 12): the lane
        additionally carries per-row cached deep activations (uncond +
        cond halves) and takes a scalar ``reuse_now`` flag the DRIVER
        decides host-side — True only when every active row's schedule
        wants reuse at its current step AND holds a valid cache (so the
        lax.cond stays a scalar branch the compiled program executes
        one side of; mixed lanes degrade to full evals, never to wrong
        math). Reuse lanes are keyed separately, so with the env off
        every lane runs this program's pre-reuse build unchanged.
        """
        fam = self.c.family
        unet = self.c.unet
        lh, lw = self._latent_hw(height, width)
        needs_xl = fam.unet.addition_embed_dim is not None
        if reuse and has_control:
            raise ValueError("DeepCache reuse lanes do not take the "
                             "ControlNet branch")

        control_net = None
        if has_control:
            from chiaswarm_tpu.models.controlnet import ControlNet

            control_net = ControlNet(fam.unet)

        def build():
            def stepper_step(params, ctx_u, ctx_c, pooled_u, pooled_c, x,
                             carry_keys, idx, start_idx, sigmas_tab,
                             ts_tab, guidance, old_denoised, active,
                             known, mask, mask_on, control_params, cond,
                             cscale, cache_u=None, cache_c=None,
                             reuse_now=None):
                params = dequantize_tree(params)
                control_params = dequantize_tree(control_params)
                sched_rows = SamplingSchedule(sigmas=sigmas_tab,
                                              timesteps=ts_tab)
                inp = scale_model_input_rows(sched_rows, x, idx)
                t = jax.vmap(lambda ts, i: ts[i])(ts_tab, idx)
                ctx = jnp.concatenate([ctx_u, ctx_c], axis=0)
                inp2 = jnp.concatenate([inp, inp], axis=0)
                t2 = jnp.concatenate([t, t], axis=0)
                added = None
                if needs_xl:
                    time_ids = jnp.asarray(
                        [height, width, 0, 0, height, width], jnp.float32
                    )[None, :].repeat(2 * batch, axis=0)
                    pooled = jnp.concatenate([pooled_u, pooled_c], axis=0)
                    added = {"time_ids": time_ids,
                             "text_embeds":
                                 pooled[:, : fam.unet.addition_pooled_dim]}
                down_res = mid_res = None
                if has_control:
                    # per-row conditioning: hint embeddings and scales are
                    # row state; the scale broadcasts (2B,1,1,1) over the
                    # zero-conv residuals — scalar-scale solo math per row
                    cond2 = jnp.concatenate([cond, cond], axis=0)
                    scale2 = jnp.concatenate(
                        [cscale, cscale]).reshape(-1, 1, 1, 1)
                    down_res, mid_res = control_net.apply(
                        control_params["net"], inp2, t2, ctx, cond2,
                        added, scale2)
                if reuse:
                    cache2 = jnp.concatenate([cache_u, cache_c], axis=0)

                    def shallow(ops):
                        inp2, t2, cache2 = ops
                        out = unet.apply(params["unet"], inp2, t2, ctx,
                                         added, cached_deep=cache2)
                        return out, cache2

                    def full(ops):
                        inp2, t2, _cache2 = ops
                        return unet.apply(params["unet"], inp2, t2, ctx,
                                          added, return_deep=True)

                    out, cache2 = jax.lax.cond(reuse_now, shallow, full,
                                               (inp2, t2, cache2))
                    cache_u_next, cache_c_next = jnp.split(cache2, 2,
                                                           axis=0)
                else:
                    out = unet.apply(params["unet"], inp2, t2, ctx, added,
                                     down_res, mid_res)
                with obs_trace.scope("sampler"):
                    eps_u, eps_c = jnp.split(out, 2, axis=0)
                    # per-row CFG combine; guidance <= 1 selects the pure
                    # conditional prediction — the CFG-free few-step mode
                    # (lcm rows, schedulers/sampling.py FEWSTEP_KINDS).
                    # For guidance > 1 the selected value is the identical
                    # expression as before, so existing rows keep their
                    # solo trajectories bit for bit.
                    g = guidance.reshape(-1, 1, 1, 1)
                    eps = jnp.where(g > 1.0, eps_u + g * (eps_c - eps_u),
                                    eps_c)
                    both = jax.vmap(jax.random.split)(carry_keys)
                    keys, skeys = both[:, 0], both[:, 1]
                    step_noise = jax.vmap(lambda k: jax.random.normal(
                        k, (lh, lw, fam.vae.latent_channels), jnp.float32)
                    )(skeys)
                    x_next, state = sampler_step_rows(
                        sampler, sched_rows, idx, x, eps,
                        SamplerState(old_denoised=old_denoised),
                        step_noise, start_idx)
                    # inpaint re-projection, selected per row: the masked
                    # variant (and its second key split) is computed for
                    # every row, applied only where mask_on — unmasked rows
                    # keep the single-split solo trajectory
                    both_m = jax.vmap(jax.random.split)(keys)
                    keys_m, mkeys = both_m[:, 0], both_m[:, 1]
                    renoise = jax.vmap(lambda k: jax.random.normal(
                        k, (lh, lw, fam.vae.latent_channels), jnp.float32)
                    )(mkeys)
                    x_masked = reproject_known_rows(
                        sched_rows, idx, x_next, known, mask, renoise)
                    m_img = mask_on.reshape(-1, 1, 1, 1)
                    x_next = jnp.where(m_img, x_masked, x_next)
                    keys = jnp.where(mask_on.reshape(-1, 1), keys_m, keys)
                    act = active.reshape(-1, 1, 1, 1)
                    x_next = jnp.where(act, x_next, x)
                    new_old = jnp.where(act, state.old_denoised, old_denoised)
                    keys = jnp.where(active.reshape(-1, 1), keys, carry_keys)
                    idx_next = idx + active.astype(idx.dtype)
                if reuse:
                    return (x_next, keys, idx_next, new_old,
                            cache_u_next, cache_c_next)
                return x_next, keys, idx_next, new_old

            return param_mesh_wrap(toplevel_jit(stepper_step), self.c.params)

        # the reuse flag joins the static key only when set, so every
        # pre-existing lane bucket keeps its historical key (and cached
        # executable) byte for byte
        statics = {"batch": batch, "height": height,
                   "width": width, "steps_cap": steps_cap,
                   "sampler": sampler, "has_control": has_control}
        if reuse:
            statics["reuse"] = True
        return GLOBAL_CACHE.cached_executable(
            static_cache_key(id(self.c), "stepper_step", statics), build)

    def stepper_flash_block_steps(self, *, batch: int, height: int,
                                  width: int, has_control: bool = False,
                                  shallow: bool = False) -> dict[str, int]:
        """Grid steps of the flash kernel one ``stepper_step_fn`` step
        runs, by kind (``ops/flash_attention.py::block_steps``): over the
        UNet's spatial self-attentions at the lane's shape that take the
        kernel, the uncond and cond halves of ``batch`` rows each (and
        ControlNet's trunk with ``has_control``; only level 0's blocks on
        a DeepCache replay, ``shallow``). Host integers, for the counter
        ``chiaswarm_flash_block_steps_total``."""
        from chiaswarm_tpu.models.unet import self_attention_sites
        from chiaswarm_tpu.ops.attention import takes_flash
        from chiaswarm_tpu.ops.flash_attention import block_steps

        cfg = self.c.unet.config
        lh, lw = self._latent_hw(height, width)
        parts = ["shallow" if shallow else "full"]
        if has_control:
            parts.append("trunk")
        total = {"whole": 0, "masked": 0}
        for part in parts:
            sites = self_attention_sites(cfg, lh, lw, part=part)
            for (tokens, heads, _head), calls in sites.items():
                if not takes_flash(tokens, tokens, cfg.attn_impl):
                    continue
                steps = block_steps(2 * batch * heads, tokens, tokens)
                for kind, n in steps.items():
                    total[kind] += calls * n
        return total

    def stepper_control_embed_fn(self, *, height: int, width: int):
        """(embed_params, cond (1, H, W, 3) in [0, 1]) -> (1, lh, lw, C0)
        hint embedding — the admission-time ControlNet prep. The embedder
        is timestep-independent, so each job's conditioning image is
        embedded ONCE here (exactly the solo program's hoisting) and the
        result rides per row as lane state."""
        fam = self.c.family

        def build():
            from chiaswarm_tpu.models.controlnet import ControlCondEmbedding

            control_embed = ControlCondEmbedding(
                fam.unet.block_out_channels[0],
                downscale=fam.vae.downscale)

            def stepper_ctrl_embed(embed_params, cond):
                return control_embed.apply(dequantize_tree(embed_params),
                                           cond)

            return toplevel_jit(stepper_ctrl_embed)

        return GLOBAL_CACHE.cached_executable(
            static_cache_key(id(self.c), "stepper_ctrl_embed",
                             {"height": height, "width": width}), build)

    def stepper_decode_fn(self, *, batch: int, height: int, width: int):
        """Latents -> uint8 images for retiring rows — dispatched
        asynchronously so the transfer/decode of finished rows overlaps
        the lane's ongoing UNet steps."""
        vae = self.c.vae

        def build():
            def stepper_decode(params, x):
                params = dequantize_tree(params)
                img = vae.apply(params["vae"], x,
                                method=AutoencoderKL.decode)
                return (jnp.clip((img + 1.0) * 127.5 + 0.5, 0.0, 255.0)
                        ).astype(jnp.uint8)

            return param_mesh_wrap(toplevel_jit(stepper_decode),
                                   self.c.params)

        return GLOBAL_CACHE.cached_executable(
            static_cache_key(id(self.c), "stepper_decode",
                             {"batch": batch, "height": height,
                              "width": width}), build)

    def __call__(self, req: GenerateRequest) -> tuple[np.ndarray, dict]:
        """Run a request. Returns (images uint8 (B,H,W,3), config dict)."""
        pending, config = self.submit(req)
        return pending.wait(), config

    def submit(self, req: GenerateRequest) -> tuple["PendingImages", dict]:
        """Dispatch a request WITHOUT blocking on the device->host image
        transfer. JAX's async dispatch returns the uint8 result array as a
        future; ``PendingImages.wait()`` fetches it. Submitting job N+1
        before waiting on job N overlaps N's host transfer with N+1's
        denoise compute; the serving loop gets the same overlap from
        depth-2 slots (core/chip_pool.py MeshSlot.depth + node/worker.py
        _slot_worker), where two blocking jobs interleave across threads.
        No reference analog — torch blocks per pipeline call."""
        fam = self.c.family
        # span shape for a solo job (chiaswarm_tpu/obs): "encode" =
        # host-side prep (tokenize, init-image VAE encode, masks),
        # "step" = executable lookup (a cold compile lands here,
        # visibly) + program dispatch; the device compute itself settles
        # in the consumer's "decode" span (PendingImages.wait) because
        # dispatch is async
        parent = obs_trace.current_span()
        enc_span = (parent.child("encode", batch=req.batch)
                    if parent is not None else None)
        try:
            # small sizes are honored like the reference (only a max clamp,
            # swarm/job_arguments.py:96-102): a 192px request generates AT
            # 192px rather than at a 256 floor and downscaled
            height, width = bucket_image_size(req.height, req.width)
            batch = bucket_batch(req.batch)
            steps = max(int(req.steps), 1)
            sampler = resolve(req.scheduler,
                              prediction_type=fam.prediction_type)
            use_cfg = req.guidance_scale > 1.0
            has_init = req.init_image is not None
            has_mask = req.mask is not None
            if has_mask and not has_init:
                raise ValueError("inpainting requires an init image with the mask")
            if fam.image_conditioned:
                if not has_init:
                    raise ValueError(
                        "this model edits an input image; start_image_uri is "
                        "required")
                if has_mask:
                    raise ValueError(
                        "instruct-pix2pix models do not take a mask")
                if req.controlnet is not None:
                    raise ValueError(
                        "instruct-pix2pix models do not support controlnet")

            start_step = 0
            init_latent = jnp.zeros((1,), jnp.float32)  # placeholder
            mask_arr = jnp.zeros((1,), jnp.float32)
            if has_init:
                if not has_mask and not fam.image_conditioned:
                    # img2img: skip the first (1-strength) of the ladder
                    # (pix2pix starts from pure noise instead)
                    start_step = img2img_start_index(steps, req.strength)
                init = np.asarray(req.init_image)
                if init.ndim == 4 and init.shape[1:3] != (height, width) or \
                   init.ndim == 3 and init.shape[:2] != (height, width):
                    init = _resize_batch(init, height, width)
                if req.init_groups is not None:
                    # coalesced jobs: encode each job's image with ITS seed
                    # through the batch-1 executable its solo run uses, then
                    # repeat over that job's rows — bitwise solo equality
                    z = jnp.concatenate([
                        jnp.repeat(self.encode_init_image(
                            init[j], height, width, enc_seed), n_rows, axis=0)
                        for j, (enc_seed, n_rows)
                        in enumerate(req.init_groups)], axis=0)
                else:
                    z = self.encode_init_image(init, height, width, req.seed)
                if z.shape[0] == 1:
                    init_latent = jnp.repeat(z, batch, axis=0)
                elif z.shape[0] == batch:
                    init_latent = z
                else:  # pad per-frame inits up to the bucketed batch
                    pad = jnp.repeat(z[-1:], batch - z.shape[0], axis=0)
                    init_latent = jnp.concatenate([z, pad], axis=0)
            if has_mask:
                lh, lw = self._latent_hw(height, width)
                f = fam.vae.downscale
                m = np.asarray(req.mask, dtype=np.float32)
                if req.init_groups is not None:
                    # per-JOB masks -> per-row stack, padded to the bucket
                    rows_m = np.concatenate([
                        np.repeat(latent_mask(m[j], lh, lw, f)[None],
                                  n_rows, axis=0)
                        for j, (_, n_rows) in enumerate(req.init_groups)])
                    if rows_m.shape[0] < batch:
                        rows_m = np.concatenate(
                            [rows_m, np.repeat(rows_m[-1:],
                                               batch - rows_m.shape[0], 0)])
                    mask_arr = jnp.asarray(rows_m)[:, :, :, None]
                else:
                    mask_arr = jnp.asarray(
                        latent_mask(m, lh, lw, f))[None, :, :, None]

            has_control = req.controlnet is not None
            control_params = {"zero": jnp.zeros((1,), jnp.float32)}
            control_cond = jnp.zeros((1,), jnp.float32)
            if has_control:
                if req.control_image is None:
                    raise ValueError("controlnet requires a conditioning image")
                cond = np.asarray(req.control_image)
                if cond.shape[:2] != (height, width):
                    cond = _resize_batch(cond, height, width)
                # hint encoder expects [0, 1] (diffusers ControlNet training
                # normalization), NOT the VAE's [-1, 1]
                cond = np.asarray(cond, np.float32)
                if req.control_image.dtype == np.uint8 or cond.max() > 1.0:
                    cond = cond / 255.0
                control_cond = jnp.asarray(np.clip(cond, 0.0, 1.0))[None]
                control_params = req.controlnet.params

            def rows(value: str | tuple[str, ...]) -> list[str]:
                vals = (list(value) if isinstance(value, (tuple, list))
                        else [value or ""] * req.batch)
                if len(vals) != req.batch:
                    raise ValueError(
                        f"{len(vals)} per-row prompts for batch {req.batch}")
                # pad to the compile bucket by repeating the last row
                return vals + [vals[-1]] * (batch - len(vals))

            ids = [jnp.asarray(i) for i in self._tokenize(rows(req.prompt))]
            neg = [jnp.asarray(i) for i in
                   self._tokenize(rows(req.negative_prompt))]

            # data parallelism: when the params live on a dp x tp mesh, seed
            # GSPMD's batch-dim propagation by placing the token inputs (and a
            # batch-shaped init) on the 'data' axis — weight sharding alone
            # leaves the batch replicated
            mesh = _params_mesh(self.c.params)
            if mesh is not None and batch % mesh.shape["data"] == 0:
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P

                row = NamedSharding(mesh, P("data", None))
                ids = [jax.device_put(i, row) for i in ids]
                neg = [jax.device_put(i, row) for i in neg]
                if getattr(init_latent, "ndim", 0) == 4 and \
                        init_latent.shape[0] == batch:
                    init_latent = jax.device_put(
                        init_latent,
                        NamedSharding(mesh, P("data", None, None, None)))

            # DeepCache (ISSUE 12): the per-job reuse schedule engages
            # only behind the env switch and never for the dual-CFG /
            # ControlNet programs; OFF means the pre-reuse executable
            # bit for bit (same static key, no reuse table traced in)
            schedule: tuple[int, ...] = ()
            if req.reuse_schedule and deepcache_enabled() \
                    and not fam.image_conditioned and not has_control:
                schedule = normalize_reuse_schedule(
                    steps, req.reuse_schedule, start_step)
            reuse = bool(schedule)

            has_noise = req.init_noise is not None
            noise_arr = jnp.zeros((1,), jnp.float32)  # placeholder
            if has_noise:
                lh, lw = self._latent_hw(height, width)
                noise_np = np.asarray(req.init_noise, np.float32)
                want = (lh, lw, fam.vae.latent_channels)
                if noise_np.ndim == 3:
                    noise_np = noise_np[None]
                if noise_np.shape[1:] != want:
                    raise ValueError(
                        f"init_noise shape {noise_np.shape[1:]} != latent "
                        f"grid {want}")
                if noise_np.shape[0] > batch:
                    raise ValueError(
                        f"init_noise carries {noise_np.shape[0]} rows but the "
                        f"request buckets to batch {batch}")
                if noise_np.shape[0] == 1:
                    noise_np = np.repeat(noise_np, batch, axis=0)
                elif noise_np.shape[0] != batch:
                    pad = np.repeat(noise_np[-1:], batch - noise_np.shape[0],
                                    axis=0)
                    noise_np = np.concatenate([noise_np, pad], axis=0)
                noise_arr = jnp.asarray(noise_np)

        except BaseException:
            # a prep failure (bad init image/mask/noise) must
            # not leave the encode span open until the trace's
            # force-close — the exported duration would absorb
            # the whole execute phase
            if enc_span is not None:
                enc_span.end()
            raise
        if enc_span is not None:
            enc_span.end()
        with span("step", steps=steps, batch=batch), span("generate"):
            # ``reuse`` joins the static set only when ON: every plain
            # request keeps its historical cache key (and executable)
            fn = self._get_fn(
                batch=batch, height=height, width=width, steps=steps,
                start_step=start_step, sampler=sampler, use_cfg=use_cfg,
                has_init=has_init, has_mask=has_mask,
                tiled=req.tiled_decode,
                has_control=has_control, has_noise=has_noise,
                **({"reuse": True} if reuse else {}),
            )
            # one independent key per batch row: fold the row index into
            # the row's seed, so row b is reproducible at ANY batch size
            # (and a coalesced job's rows match what its solo run would
            # produce)
            pairs = (list(req.sample_seed_rows) if req.sample_seed_rows
                     else [(req.seed, i) for i in range(req.batch)])
            if len(pairs) != req.batch:
                raise ValueError(
                    f"{len(pairs)} sample_seed_rows for batch {req.batch}")
            pairs += [pairs[-1]] * (batch - len(pairs))  # bucket padding
            sample_keys = jnp.stack(
                [jax.random.fold_in(key_for_seed(int(s)), int(r))
                 for s, r in pairs])
            args = [
                self.c.params,
                ids,
                neg,
                sample_keys,
                jnp.float32(req.guidance_scale),
                init_latent,
                mask_arr,
                control_params,
                control_cond,
                jnp.float32(req.control_scale),
                jnp.float32(req.image_guidance_scale),
                noise_arr,
            ]
            if reuse:
                tab = np.zeros(steps, bool)
                tab[list(schedule)] = True
                args.append(jnp.asarray(tab))
            img = fn(*args)
        # step-collapse accounting (ISSUE 12): FULL UNet evals each image
        # pays, plus the live counter/histogram families
        full_evals = (steps - start_step) - len(schedule)
        _UNET_EVALS.inc(req.batch * full_evals, mode="full")
        if schedule:
            _UNET_EVALS.inc(req.batch * len(schedule), mode="reuse")
            _STEPS_SKIPPED.inc(req.batch * len(schedule))
        for _ in range(req.batch):
            _EVALS_PER_IMAGE.observe(full_evals)
        config = {
            "model_name": self.c.model_name,
            "family": fam.name,
            "scheduler": sampler.kind,
            "steps": steps,
            # ladder position actually executed (img2img strength maps to
            # a start index; the quantization is an observable contract)
            "denoise_steps": steps - start_step,
            "unet_evals": full_evals,
            "steps_skipped": len(schedule),
            "guidance_scale": float(req.guidance_scale),
            "size": [req.height, req.width],
            "compiled_size": [height, width],
            "batch": batch,
            "mode": ("pix2pix" if fam.image_conditioned else
                     "inpaint" if has_mask else
                     "img2img" if has_init else "txt2img"),
        }
        if schedule:
            config["reuse_schedule"] = list(schedule)
        if fam.image_conditioned:
            config["image_guidance_scale"] = float(req.image_guidance_scale)
        if has_control:
            config["controlnet"] = req.controlnet.model_name
            config["controlnet_scale"] = float(req.control_scale)
        return PendingImages(img, (height, width),
                             (req.height, req.width), req.batch), config
