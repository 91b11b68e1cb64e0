"""The plain reference against the program on the CPU at tiny sizes, the
lower-precision control that ``correct`` has to fail, and the FLOP
function against the costed HLO."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import flops, hlo, reference, weights  # noqa: E402
from perfbench.kinds import diffusion  # noqa: E402

JOB = dict(prompt="amber harbor dusk lantern", seed=77, steps=17,
           guidance=7.5, height=64, width=64)


def load(name):
    return json.loads(
        (ROOT / "perfbench" / "configs" / f"{name}.json").read_text())


@pytest.fixture(scope="module", params=["tiny-64", "tinyxl-64"])
def served(request):
    """(config, params, the program's own image of JOB) - the solo
    pipeline here; lanes equal it row for row (tests/test_stepper.py)."""
    from chiaswarm_tpu.pipelines.diffusion import (
        DiffusionPipeline,
        GenerateRequest,
    )
    config = load(request.param)
    components, params = diffusion.build_components(
        config, 2 ** 31 + 9, None)
    image, _ = DiffusionPipeline(components)(GenerateRequest(
        prompt=JOB["prompt"], steps=JOB["steps"], seed=JOB["seed"],
        guidance_scale=JOB["guidance"], height=64, width=64))
    return config, params, image[0]


def test_reference_agrees_with_the_program_to_rounding(served):
    config, params, image = served
    want = reference.generate(params, config, **JOB)
    # float32 on both sides: what is left is the uint8 rounding (0.5)
    assert np.abs(image.astype(np.float32) - want).max() < 1.0
    assert diffusion.image_gap(image, want) \
        < config["compare"]["image_gap_limit"]


def test_control_one_precision_down_comes_out_not_correct(served):
    """The tiny configurations state float32, so their control is the
    reference in bfloat16, put in the program's place."""
    config, params, _ = served
    want = reference.generate(params, config, **JOB)
    control = reference.generate(params, config, precision="bfloat16",
                                 **JOB)
    limit = config["compare"]["image_gap_limit"]
    assert diffusion.image_gap(np.clip(np.round(control), 0, 255), want) \
        > 1.2 * limit
    lower = reference.generate(params, config, precision="fp8", **JOB)
    assert diffusion.image_gap(lower, want) > 10 * limit


def test_same_seed_same_weights_and_no_zero_leaf():
    from chiaswarm_tpu.pipelines.components import abstract_params

    tree = abstract_params("tiny")
    a = weights.make_params(tree, 2 ** 31 + 9, dtype="float32")
    b = weights.make_params(tree, 2 ** 31 + 9, dtype="float32")
    c = weights.make_params(tree, 5, dtype="float32")
    import jax

    la, lb, lc = (jax.tree.leaves(t) for t in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert any(not np.array_equal(x, y) for x, y in zip(la, lc))
    assert all(np.abs(np.asarray(x)).max() > 0 for x in la)


def test_tokens_are_the_served_tokenizers():
    from chiaswarm_tpu.models.tokenizer import HashTokenizer

    text = "violet mountain fog copper tower"
    got = reference.hash_tokens(text, 49408, 49407, 77)
    assert got.tolist() == HashTokenizer(49408, 77, 49407).encode(text)
    with pytest.raises(ValueError):
        reference.hash_tokens("Don't", 49408, 49407, 77)


@pytest.mark.parametrize("name", ["tiny-64", "tinyxl-64"])
def test_flop_function_agrees_with_the_costed_hlo(name):
    """flops.unet_forward against the sum of conv and dot FLOPs that
    hlo.parse_hlo_text reads from the compiled UNet. Margin 2%: the
    function leaves out nothing but element-wise work, which the HLO
    count leaves out too."""
    import jax
    import jax.numpy as jnp

    from chiaswarm_tpu.models.configs import FAMILIES
    from chiaswarm_tpu.models.unet import UNet
    from chiaswarm_tpu.pipelines.components import abstract_params

    config = load(name)
    family = FAMILIES[config["program_family"]]
    params = abstract_params(family)["unet"]
    added = None
    if family.unet.addition_embed_dim:
        added = {"time_ids": jnp.zeros((1, 6)),
                 "text_embeds": jnp.zeros(
                     (1, family.unet.addition_pooled_dim))}
    compiled = jax.jit(UNet(family.unet).apply).lower(
        params, jnp.zeros((1, 8, 8, 4)), jnp.zeros((1,)),
        jnp.zeros((1, 77, family.unet.cross_attention_dim)), added
    ).compile()
    text = hlo.compiled_hlo_text(compiled)
    inside = hlo.called_computations(text)
    counted = sum(c["flops"] for c in hlo.parse_hlo_text(text).values()
                  if c["computation"] not in inside)
    assert counted > 0
    # 8x8 latents are a 16 px image under the tiny VAE (downscale 2)
    assert flops.unet_forward(config, 16, 16) == pytest.approx(counted,
                                                               rel=0.02)


def test_published_sizes_give_the_published_scale():
    sdxl, sd15 = load("sdxl-1024"), load("sd15-512")
    # ~6.8 TFLOP an SDXL UNet evaluation at 1024 px, ~0.8 for SD1.5 at 512
    assert 6.0e12 < flops.unet_forward(sdxl, 1024, 1024) < 7.5e12
    assert 0.7e12 < flops.unet_forward(sd15, 512, 512) < 0.9e12
    assert flops.job(sdxl, 30, 1024, 1024) \
        > 60 * flops.unet_forward(sdxl, 1024, 1024)
