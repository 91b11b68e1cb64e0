"""Jitted txt2audio pipeline (AudioLDM-class mel-latent diffusion).

Capability parity with swarm/audio/audioldm.py:12-36 — the reference runs
``cvssp/audioldm-s-full-v2`` (20 steps, 10 s of 16 kHz audio) and encodes
wav -> mp3 on the host. TPU-first redesign: ONE compiled program runs
text encode (pooled embedding conditioning) -> lax.scan denoise over the
mel-spectrogram latent -> VAE decode -> HiFiGAN vocoder, emitting the
waveform straight from the chip. Host work is tokenization + WAV framing
(workloads/audio.py; this image has no ffmpeg, so artifacts are
audio/wav — content negotiation reports the type).

Audio-specific shapes: the "image" is a (T_frames, n_mel) log-mel
spectrogram with ONE channel; sequence length rides the H axis so the
existing NHWC UNet/VAE stack applies unchanged. Duration buckets quantize
T_frames so compile cache entries stay bounded.
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
from chiaswarm_tpu.models.clap import ClapTextConfig, ClapTextEncoder
from chiaswarm_tpu.models.configs import (
    UNetConfig,
    VAEConfig,
)
from chiaswarm_tpu.models.tokenizer import HashTokenizer
from chiaswarm_tpu.models.unet import UNet
from chiaswarm_tpu.models.vae import AutoencoderKL
from chiaswarm_tpu.models.vocoder import HifiGan, HifiGanConfig
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
class AudioFamily:
    """Architecture of one AudioLDM-class checkpoint."""

    name: str
    text_encoder: ClapTextConfig      # CLAP text tower (RoBERTa layout)
    unet: UNetConfig                  # over mel latents, FiLM-conditioned
    vae: VAEConfig                    # 1-channel mel autoencoder
    vocoder: HifiGanConfig
    n_mel: int = 64
    beta_schedule: str = "scaled_linear"
    prediction_type: str = "epsilon"


AUDIOLDM = AudioFamily(
    name="audioldm",
    text_encoder=ClapTextConfig(),    # laion/clap-htsat defaults (12x768)
    unet=UNetConfig(
        sample_channels=8, out_channels=8,
        block_out_channels=(128, 256, 384, 640),
        transformer_depth=(1, 1, 1, 1),
        attention_head_dim=32, head_dim_is_count=False,
        # AudioLDM's UNet has NO text cross-attention: the normalized CLAP
        # text_embeds condition every resnet through a simple-projection
        # class embedding concatenated with the time embedding
        cross_attention_dim=None,
        class_proj_dim=512, class_embeddings_concat=True,
    ),
    vae=VAEConfig(in_channels=1, latent_channels=8,
                  block_out_channels=(128, 256, 512),
                  scaling_factor=0.9227),
    vocoder=HifiGanConfig(),
)

TINY_AUDIO = AudioFamily(
    name="tiny_audio",
    # max_length must fit the tiny 130-row position table (the class
    # default is the published 512, which would silently clamp gathers)
    text_encoder=ClapTextConfig(
        vocab_size=1000, hidden_size=32, intermediate_size=64,
        num_layers=2, num_heads=4, projection_dim=32,
        max_position_embeddings=130, max_length=77),
    unet=UNetConfig(
        sample_channels=8, out_channels=8,
        block_out_channels=(32, 64), layers_per_block=1,
        transformer_depth=(1, 1), attention_head_dim=4,
        head_dim_is_count=True, cross_attention_dim=None,
        class_proj_dim=32, class_embeddings_concat=True, dtype="float32"),
    vae=VAEConfig(in_channels=1, latent_channels=8,
                  block_out_channels=(16, 32), layers_per_block=1,
                  dtype="float32"),
    vocoder=HifiGanConfig(model_in_dim=16, upsample_initial_channel=32,
                          upsample_rates=(4, 4), upsample_kernel_sizes=(8, 8),
                          resblock_kernel_sizes=(3,),
                          resblock_dilation_sizes=((1, 3),)),
    n_mel=16,
)

AUDIO_FAMILIES = {f.name: f for f in (AUDIOLDM, TINY_AUDIO)}


def get_audio_family(model_name: str) -> AudioFamily:
    low = (model_name or "").lower()
    tail = low.rsplit("/", 1)[-1]
    if low in AUDIO_FAMILIES:
        return AUDIO_FAMILIES[low]
    if tail in AUDIO_FAMILIES:
        return AUDIO_FAMILIES[tail]
    return AUDIO_FAMILIES["audioldm"]


@dataclasses.dataclass
class AudioComponents:
    family: AudioFamily
    model_name: str
    tokenizer: Any
    text_encoder: ClapTextEncoder
    unet: UNet
    vae: AutoencoderKL
    vocoder: HifiGan
    params: dict[str, Any]  # keys: text_encoder, unet, vae, vocoder

    @classmethod
    def random(cls, family: AudioFamily | str, seed: int = 0,
               model_name: str | None = None) -> "AudioComponents":
        if isinstance(family, str):
            family = AUDIO_FAMILIES[family]
        key = jax.random.PRNGKey(seed)
        te = ClapTextEncoder(family.text_encoder)
        unet = UNet(family.unet)
        vae = AutoencoderKL(family.vae)
        voc = HifiGan(family.vocoder)
        tcfg = family.text_encoder
        tokenizer = HashTokenizer(tcfg.vocab_size, tcfg.max_length,
                                  eos_id=tcfg.eos_token_id,
                                  bos_id=tcfg.bos_token_id,
                                  pad_id=tcfg.pad_token_id)
        ids = jnp.zeros((1, tcfg.max_length), jnp.int32)
        key, k1, k2, k3, k4 = jax.random.split(key, 5)
        mel_lat = family.n_mel // family.vae.downscale
        params = {
            "text_encoder": jax.jit(te.init)(k1, ids),
            "unet": jax.jit(unet.init)(
                k2, jnp.zeros((1, 8, mel_lat, family.unet.sample_channels)),
                jnp.zeros((1,)), None,
                class_labels=jnp.zeros((1, family.unet.class_proj_dim))),
            "vae": jax.jit(vae.init)(
                k3, jnp.zeros((1, 8, family.n_mel, 1))),
            "vocoder": jax.jit(voc.init)(
                k4, jnp.zeros((1, 8, family.vocoder.model_in_dim))),
        }
        return cls(family=family,
                   model_name=model_name or f"random/{family.name}",
                   tokenizer=tokenizer, text_encoder=te, unet=unet, vae=vae,
                   vocoder=voc, params=params)

    def param_bytes(self) -> int:
        leaves = jax.tree.leaves(self.params)
        return sum(leaf.size * leaf.dtype.itemsize for leaf in leaves)


class AudioPipeline:
    """Resident compile-cached txt2audio executor."""

    def __init__(self, components: AudioComponents,
                 attn_impl: str = "auto") -> None:
        self.c = components
        fam = components.family
        if attn_impl not in ("auto", fam.unet.attn_impl):
            components.unet = UNet(dataclasses.replace(
                fam.unet, attn_impl=attn_impl))
        self.schedule_config = ScheduleConfig(
            beta_schedule=fam.beta_schedule,
            prediction_type=fam.prediction_type,
        )
        self.noise_schedule = make_noise_schedule(self.schedule_config)

    def _frames_for(self, duration_s: float) -> int:
        """Duration -> mel frame count, bucketed to limit compile cache
        growth: multiples of 64 latent-frames (VAE+UNet need the T axis
        divisible by total downscale)."""
        fam = self.c.family
        sr = fam.vocoder.sampling_rate
        hop = fam.vocoder.hop_length
        frames = int(round(duration_s * sr / hop))
        quantum = fam.vae.downscale * (2 ** (
            len(fam.unet.block_out_channels) - 1))
        return max(quantum, (frames + quantum - 1) // quantum * quantum)

    def _build_fn(self, *, batch: int, frames: int, steps: int, sampler,
                  use_cfg: bool):
        fam = self.c.family
        te, unet, vae, voc = (self.c.text_encoder, self.c.unet, self.c.vae,
                              self.c.vocoder)
        sched = make_sampling_schedule(self.noise_schedule, steps, sampler)
        f = fam.vae.downscale
        lt, lm = frames // f, fam.n_mel // f
        latent_ch = fam.vae.latent_channels

        def fn(params, ids, neg_ids, key, guidance):
            # CLAP conditioning (the serving pipeline's exact protocol):
            # projected text_embeds, L2-normalized, FiLM-injected into the
            # UNet as float class labels — no cross-attention sequence
            def embed(token_ids):
                _, proj = te.apply(params["text_encoder"], token_ids)
                return proj / jnp.maximum(
                    jnp.linalg.norm(proj, axis=-1, keepdims=True), 1e-12)

            cond = embed(ids)
            if use_cfg:
                cond = jnp.concatenate([embed(neg_ids), cond], axis=0)

            key, nkey = jax.random.split(key)
            x = jax.random.normal(nkey, (batch, lt, lm, latent_ch),
                                  jnp.float32) * sched.sigmas[0]

            def body(carry, i):
                x, state, key = carry
                inp = scale_model_input(sched, x, i)
                if use_cfg:
                    inp2 = jnp.concatenate([inp, inp], axis=0)
                    t2 = sched.timesteps[i][None].repeat(2 * batch, axis=0)
                    out = unet.apply(params["unet"], inp2, t2, None,
                                     class_labels=cond)
                    eps_u, eps_c = jnp.split(out, 2, axis=0)
                    eps = eps_u + guidance * (eps_c - eps_u)
                else:
                    t1 = sched.timesteps[i][None].repeat(batch, axis=0)
                    eps = unet.apply(params["unet"], inp, t1, None,
                                     class_labels=cond)
                key, skey = jax.random.split(key)
                noise = jax.random.normal(skey, x.shape, jnp.float32)
                x, state = sampler_step(sampler, sched, i, x, eps, state,
                                        noise=noise, start_index=0)
                return (x, state, key), None

            (x, _, _), _ = jax.lax.scan(
                body, (x, init_sampler_state(x), key), jnp.arange(steps))

            mel = vae.apply(params["vae"], x, method=AutoencoderKL.decode)
            return voc.apply(params["vocoder"], mel[..., 0])

        return param_mesh_wrap(toplevel_jit(fn), self.c.params)

    def _get_fn(self, **static):
        return GLOBAL_CACHE.cached_executable(
            static_cache_key(id(self.c), "audio", static),
            lambda: self._build_fn(**static))

    def __call__(self, prompt: str, negative_prompt: str = "",
                 steps: int = 20, guidance_scale: float = 2.5,
                 duration_s: float = 10.0, batch: int = 1, seed: int = 0,
                 scheduler: str | None = None) -> tuple[np.ndarray, int, dict]:
        """Returns (waveform float32 (B, samples), sample_rate, config)."""
        fam = self.c.family
        batch = bucket_batch(max(1, batch))
        frames = self._frames_for(duration_s)
        sampler = resolve(scheduler, prediction_type=fam.prediction_type)
        use_cfg = guidance_scale > 1.0
        tok = self.c.tokenizer
        ids = jnp.asarray(tok.encode_batch([prompt] * batch))
        neg = jnp.asarray(tok.encode_batch([negative_prompt or ""] * batch))

        fn = self._get_fn(batch=batch, frames=frames, steps=int(steps),
                          sampler=sampler, use_cfg=use_cfg)
        wav = fn(self.c.params, ids, neg, key_for_seed(seed),
                 jnp.float32(guidance_scale))
        wav = np.asarray(jax.device_get(wav))
        sr = fam.vocoder.sampling_rate
        want = int(round(duration_s * sr))
        wav = wav[:, :want] if wav.shape[1] >= want else wav
        config = {
            "model_name": self.c.model_name,
            "family": fam.name,
            "mode": "txt2audio",
            "steps": int(steps),
            "guidance_scale": float(guidance_scale),
            "duration_s": round(wav.shape[1] / sr, 3),
            "sample_rate": sr,
            "scheduler": sampler.kind,
        }
        return wav, sr, config
