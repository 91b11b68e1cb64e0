"""ControlNet: model, pipeline integration, converter naming, workload path.

Reference behaviors covered: ControlNet loaded next to the pipeline and run
in the denoise hot loop (swarm/diffusion/diffusion_func.py:29-39,96), the
preprocessed-input echo artifact (:36-39), and the job_arguments rewiring
(swarm/job_arguments.py:116-124).
"""

import numpy as np
import pytest

from chiaswarm_tpu.pipelines import (
    Components,
    ControlNetBundle,
    DiffusionPipeline,
    GenerateRequest,
)


@pytest.fixture(scope="module")
def tiny_pipeline():
    return DiffusionPipeline(Components.random("tiny", seed=0))


@pytest.fixture(scope="module")
def tiny_controlnet():
    return ControlNetBundle.random("tiny", seed=1)


def _cond_image():
    rng = np.random.default_rng(7)
    return rng.integers(0, 255, (64, 64, 3), dtype=np.uint8)


def test_zero_init_controlnet_is_noop(tiny_pipeline, tiny_controlnet):
    """A ControlNet starts training with zero output convs, and with them
    generation must match plain txt2img exactly (the zero-conv design
    invariant). A random bundle's heads are non-zero — it stands in for
    a trained checkpoint — so this zeroes them."""
    import dataclasses

    import jax

    net = dict(tiny_controlnet.params["net"]["params"])
    for key in net:
        if key.startswith("controlnet_"):
            net[key] = jax.tree.map(np.zeros_like, net[key])
    untrained = dataclasses.replace(
        tiny_controlnet, model_name="untrained/controlnet",
        params={**tiny_controlnet.params, "net": {"params": net}})

    base = GenerateRequest(prompt="a fox", steps=3, height=64, width=64,
                          seed=5, guidance_scale=5.0)
    plain, _ = tiny_pipeline(base)
    controlled, config = tiny_pipeline(dataclasses.replace(
        base, controlnet=untrained, control_image=_cond_image()))
    assert np.array_equal(plain, controlled)
    assert config["controlnet"] == untrained.model_name


def test_trained_controlnet_steers(tiny_pipeline, tiny_controlnet):
    """With non-zero output convs the residuals must change the image, and
    conditioning_scale=0 must recover the uncontrolled output without
    recompiling (scale is traced)."""
    import jax

    # fabricate "trained" zero convs: bump every controlnet head kernel
    params = jax.tree.map(lambda x: x, tiny_controlnet.params)  # copy

    def bump(tree):
        return jax.tree.map(lambda x: x + 0.05, tree)

    net = dict(params["net"]["params"])
    for key in list(net):
        if key.startswith("controlnet_"):
            net[key] = bump(net[key])
    params["net"] = {"params": net}
    trained = ControlNetBundle(family=tiny_controlnet.family,
                               model_name="trained/controlnet",
                               params=params)

    base = GenerateRequest(prompt="a fox", steps=3, height=64, width=64,
                          seed=5, guidance_scale=5.0)
    plain, _ = tiny_pipeline(base)
    import dataclasses

    steered, _ = tiny_pipeline(dataclasses.replace(
        base, controlnet=trained, control_image=_cond_image()))
    assert not np.array_equal(plain, steered)

    from chiaswarm_tpu.core.compile_cache import GLOBAL_CACHE

    before = GLOBAL_CACHE.executables.stats["misses"]
    zeroed, _ = tiny_pipeline(dataclasses.replace(
        base, controlnet=trained, control_image=_cond_image(),
        control_scale=0.0))
    assert GLOBAL_CACHE.executables.stats["misses"] == before
    assert np.array_equal(plain, zeroed)


def test_controlnet_requires_cond_image(tiny_pipeline, tiny_controlnet):
    with pytest.raises(ValueError, match="conditioning image"):
        tiny_pipeline(GenerateRequest(prompt="x", steps=2, height=64,
                                      width=64, controlnet=tiny_controlnet))


def test_convert_controlnet_naming():
    """Torch-layout ControlNetModel keys land on the bundle's param paths."""
    from chiaswarm_tpu.convert.torch_to_flax import convert_controlnet
    from chiaswarm_tpu.models.configs import FAMILIES

    cfg = FAMILIES["tiny"].unet
    state = {
        "controlnet_cond_embedding.conv_in.weight": np.zeros((16, 3, 3, 3)),
        "controlnet_cond_embedding.conv_in.bias": np.zeros((16,)),
        "controlnet_cond_embedding.blocks.0.weight": np.zeros((16, 16, 3, 3)),
        "controlnet_cond_embedding.conv_out.weight": np.zeros((32, 256, 3, 3)),
        "controlnet_down_blocks.0.weight": np.zeros((32, 32, 1, 1)),
        "controlnet_down_blocks.0.bias": np.zeros((32,)),
        "controlnet_mid_block.weight": np.zeros((64, 64, 1, 1)),
        "conv_in.weight": np.zeros((32, 4, 3, 3)),
        "time_embedding.linear_1.weight": np.zeros((128, 32)),
        "down_blocks.0.resnets.0.conv1.weight": np.zeros((32, 32, 3, 3)),
        "mid_block.resnets.0.conv1.weight": np.zeros((64, 64, 3, 3)),
    }
    out = convert_controlnet(state, cfg)
    embed = out["embed"]["params"]
    net = out["net"]["params"]
    assert embed["conv_in"]["kernel"].shape == (3, 3, 3, 16)
    assert embed["blocks_0"]["kernel"].shape == (3, 3, 16, 16)
    assert embed["conv_out"]["kernel"].shape == (3, 3, 256, 32)
    assert net["controlnet_down_blocks_0"]["kernel"].shape == (1, 1, 32, 32)
    assert net["controlnet_mid_block"]["kernel"].shape == (1, 1, 64, 64)
    assert net["conv_in"]["kernel"].shape == (3, 3, 4, 32)
    assert net["time_embedding"]["linear_1"]["kernel"].shape == (32, 128)
    assert net["down_0_resnets_0"]["conv1"]["kernel"].shape == (3, 3, 32, 32)
    assert net["mid_resnets_0"]["conv1"]["kernel"].shape == (3, 3, 64, 64)


@pytest.mark.slow
def test_controlnet_residual_count_matches_unet_skips(tiny_controlnet):
    """The control branch must emit exactly one residual per UNet skip."""
    import jax
    import jax.numpy as jnp

    from chiaswarm_tpu.models.configs import FAMILIES
    from chiaswarm_tpu.models.controlnet import (
        ControlCondEmbedding,
        ControlNet,
    )

    fam = FAMILIES["tiny"]
    cfg = fam.unet
    net = ControlNet(cfg)
    embed = ControlCondEmbedding(cfg.block_out_channels[0],
                                 downscale=fam.vae.downscale)
    f = fam.vae.downscale
    latent = jnp.zeros((1, 8, 8, cfg.sample_channels))
    cond = jnp.zeros((1, 8 * f, 8 * f, 3))
    ctx = jnp.zeros((1, 77, cfg.cross_attention_dim))
    cond_emb = embed.apply(tiny_controlnet.params["embed"], cond)
    down, mid = net.apply(tiny_controlnet.params["net"], latent,
                          jnp.zeros((1,)), ctx, cond_emb)
    n_levels = len(cfg.block_out_channels)
    expected = 1 + n_levels * cfg.layers_per_block + (n_levels - 1)
    assert len(down) == expected
    assert mid.shape[-1] == cfg.block_out_channels[-1]


@pytest.mark.slow
def test_workload_controlnet_echo_artifact():
    """diffusion_callback with controlnet_model_name: conditioning steers a
    txt2img pass and the preprocessed input echoes back as an artifact."""
    from chiaswarm_tpu.node.registry import ModelRegistry
    from chiaswarm_tpu.workloads.diffusion import diffusion_callback

    registry = ModelRegistry(catalog=[], allow_random=True)
    artifacts, config = diffusion_callback(
        "slot0", "random/tiny", seed=3, registry=registry,
        prompt="a bridge", num_inference_steps=2, height=64, width=64,
        image=_cond_image(),
        controlnet_model_name="random/controlnet-tiny",
        save_preprocessed_input=True,
    )
    assert "primary" in artifacts
    assert "preprocessed_input" in artifacts
    assert config["mode"] == "txt2img"  # control image is NOT an init image
    assert config["controlnet"] == "random/controlnet-tiny"
