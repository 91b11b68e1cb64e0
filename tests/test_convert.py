"""Checkpoint conversion round-trip tests: random Flax params -> exported
HF-style torch snapshot (tests/torch_export.py, an independent inverse
mapping) -> convert.load_checkpoint -> identical tree."""

import jax
import numpy as np
import pytest

from chiaswarm_tpu.convert import load_checkpoint, merge_lora
from chiaswarm_tpu.pipelines.components import Components
from chiaswarm_tpu.pipelines.diffusion import DiffusionPipeline, GenerateRequest

from tests.torch_export import write_checkpoint


def _tree_paths(tree, prefix=""):
    out = {}
    for key, value in tree.items():
        path = f"{prefix}/{key}" if prefix else key
        if isinstance(value, dict):
            out.update(_tree_paths(value, path))
        else:
            out[path] = np.asarray(value)
    return out


@pytest.mark.parametrize("family", ["tiny", "tiny_xl", "tiny_up4"])
def test_checkpoint_roundtrip(tmp_path, family):
    src = Components.random(family, seed=7)
    write_checkpoint(tmp_path, src)
    converted = load_checkpoint(tmp_path, src.family)

    for module in src.params:
        want = _tree_paths(src.params[module])
        got = _tree_paths(converted[module])
        assert set(got) == set(want), (
            module,
            sorted(set(want) - set(got))[:5],
            sorted(set(got) - set(want))[:5],
        )
        for path, value in want.items():
            np.testing.assert_allclose(
                got[path], np.asarray(value), rtol=1e-6, atol=1e-6,
                err_msg=f"{module}/{path}",
            )


@pytest.mark.slow
def test_converted_checkpoint_generates(tmp_path):
    src = Components.random("tiny", seed=3)
    write_checkpoint(tmp_path, src)
    loaded = Components.from_checkpoint(tmp_path, "tiny", "tiny")
    pipe_src = DiffusionPipeline(src)
    pipe_new = DiffusionPipeline(loaded)
    req = GenerateRequest(prompt="same weights", steps=3, height=64,
                          width=64, seed=5, guidance_scale=4.0)
    a, _ = pipe_src(req)
    b, _ = pipe_new(req)
    np.testing.assert_array_equal(a, b)


def test_lora_merge_diffusers_format():
    # fp32 weights: the merge arithmetic is checked to 1e-5
    src = Components.random("tiny", seed=1, dtype="float32")
    kernel_path = ("down_0_attentions_0", "transformer_blocks_0", "attn1",
                   "to_q", "kernel")
    tree = src.params["unet"]["params"]
    orig = np.asarray(tree["down_0_attentions_0"]["transformer_blocks_0"]
                      ["attn1"]["to_q"]["kernel"])
    inner, out = orig.shape
    rank = 2
    rng = np.random.default_rng(0)
    down = rng.normal(size=(rank, inner)).astype(np.float32)
    up = rng.normal(size=(out, rank)).astype(np.float32)
    lora = {
        "down_blocks.0.attentions.0.transformer_blocks.0.attn1.processor"
        ".to_q_lora.down.weight": down,
        "down_blocks.0.attentions.0.transformer_blocks.0.attn1.processor"
        ".to_q_lora.up.weight": up,
    }
    merged, count = merge_lora(src.params["unet"], lora, scale=0.5,
                               n_levels=2)
    assert count == 1
    got = np.asarray(merged["params"]["down_0_attentions_0"]
                     ["transformer_blocks_0"]["attn1"]["to_q"]["kernel"])
    np.testing.assert_allclose(got, orig + 0.5 * (up @ down).T,
                               rtol=1e-5, atol=1e-5)


def test_lora_merge_peft_format():
    src = Components.random("tiny", seed=2, dtype="float32")
    tree = src.params["unet"]["params"]
    orig = np.asarray(tree["mid_attention"]["transformer_blocks_0"]
                      ["attn2"]["to_v"]["kernel"])
    inner, out = orig.shape
    rng = np.random.default_rng(1)
    a = rng.normal(size=(3, inner)).astype(np.float32)
    b = rng.normal(size=(out, 3)).astype(np.float32)
    lora = {
        "unet.mid_block.attentions.0.transformer_blocks.0.attn2.to_v"
        ".lora_A.weight": a,
        "unet.mid_block.attentions.0.transformer_blocks.0.attn2.to_v"
        ".lora_B.weight": b,
    }
    merged, count = merge_lora(src.params["unet"], lora, scale=1.0,
                               n_levels=2)
    assert count == 1
    got = np.asarray(merged["params"]["mid_attention"]
                     ["transformer_blocks_0"]["attn2"]["to_v"]["kernel"])
    np.testing.assert_allclose(got, orig + (b @ a).T, rtol=1e-5, atol=1e-5)


def test_lora_incompatible_raises():
    src = Components.random("tiny", seed=4)
    with pytest.raises(ValueError, match="incompatible"):
        merge_lora(src.params["unet"],
                   {"bogus.to_q.lora_A.weight": np.zeros((2, 8), np.float32),
                    "bogus.to_q.lora_B.weight": np.zeros((8, 2), np.float32)},
                   n_levels=2)
