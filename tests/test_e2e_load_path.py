"""End-to-end production load path at the REAL SD1.5 layout, offline.

VERDICT r3 item #2: conversion was tested per-module and rendering from
``Components.random`` — but the path a real node exercises (safetensors
snapshot on disk -> registry conversion/load -> jitted render -> artifact
envelope, the equivalent of the reference's
``DiffusionPipeline.from_pretrained`` + callback + ``make_result`` chain,
swarm/diffusion/diffusion_func.py:41-96 + swarm/output_processor.py) had
never run as ONE piece. This test authors a full SD1.5-layout snapshot on
disk — real tensor names (text tower named by transformers' own
CLIPTextModel at the published config; UNet/VAE in the diffusers naming
the converter round-trip suite pins), real shapes, safetensors, a CLIP
vocab.json/merges.txt — then runs the production path end to end and
checks the converted text tower against the torch oracle INSIDE the
loaded pipeline.

Slow tier: full-config SD1.5 on the CPU test platform is compile-heavy.
The weights-gated image-level PSNR proof stays in test_real_checkpoint.py.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

pytestmark = pytest.mark.slow

_SD15_CLIP_L = dict(vocab_size=49408, hidden_size=768,
                    intermediate_size=3072, num_hidden_layers=12,
                    num_attention_heads=12, max_position_embeddings=77,
                    hidden_act="quick_gelu", projection_dim=768)


def _write_clip_tokenizer(model_root) -> None:
    """A coherent mini CLIP-BPE vocab at the REAL special-token ids (the
    49408-row embedding's BOS/EOS rows must be hit by real encodes)."""
    merges = [("h", "i</w>"), ("c", "a"), ("ca", "t</w>")]
    tokens = {"<|startoftext|>": 49406, "<|endoftext|>": 49407}
    body = (["hi</w>", "cat</w>", "h", "i</w>", "c", "a", "t</w>"]
            + [chr(c) for c in range(ord("a"), ord("z") + 1)]
            + [chr(c) + "</w>" for c in range(ord("a"), ord("z") + 1)])
    for i, tok in enumerate(body):
        tokens.setdefault(tok, i)
    tok_dir = model_root / "tokenizer"
    tok_dir.mkdir(parents=True, exist_ok=True)
    with open(tok_dir / "vocab.json", "w", encoding="utf-8") as fh:
        json.dump(tokens, fh)
    with open(tok_dir / "merges.txt", "w", encoding="utf-8") as fh:
        fh.write("#version: 0.2\n")
        for a, b in merges:
            fh.write(f"{a} {b}\n")


def test_sd15_snapshot_to_artifact_envelope(tmp_path, monkeypatch):
    from safetensors.numpy import save_file

    import jax

    from chiaswarm_tpu.core.chip_pool import ChipPool
    from chiaswarm_tpu.core.mesh import MeshSpec
    from chiaswarm_tpu.models.configs import SD15
    from chiaswarm_tpu.node.executor import synchronous_do_work
    from chiaswarm_tpu.node.registry import ModelRegistry, model_dir
    from chiaswarm_tpu.pipelines.components import Components

    from tests.torch_export import export_unet, export_vae

    monkeypatch.setenv("SDAAS_ROOT", str(tmp_path))
    name = "runwayml/stable-diffusion-v1-5"
    root = model_dir(name)

    # ---- author the snapshot: real layout, random values ---------------
    torch.manual_seed(0)
    text_model = transformers.CLIPTextModel(
        transformers.CLIPTextConfig(**_SD15_CLIP_L)).eval()
    (root / "text_encoder").mkdir(parents=True)
    save_file({k: v.detach().numpy()
               for k, v in text_model.state_dict().items()},
              str(root / "text_encoder" / "model.safetensors"))

    src = Components.random(SD15, seed=0)
    for sub, state in (
        ("unet", export_unet(src.params["unet"], 4)),
        ("vae", export_vae(src.params["vae"], 4)),
    ):
        (root / sub).mkdir(parents=True)
        save_file({k: np.ascontiguousarray(np.asarray(v, np.float32))
                   for k, v in state.items()},
                  str(root / sub / "diffusion_pytorch_model.safetensors"))
    _write_clip_tokenizer(root)
    del src

    # ---- production path: registry conversion/load ---------------------
    registry = ModelRegistry(
        catalog=[{"name": name, "family": "sd15"}], allow_random=False)
    pipe = registry.pipeline(name)
    comps = pipe.c

    # the loaded tokenizer is the real CLIP BPE over the snapshot's files
    ids = comps.tokenizers[0].encode("hi cat")
    assert ids[0] == 49406 and 49407 in ids[1:]

    # converted text tower vs the torch oracle INSIDE the loaded pipeline
    # (non-circular: transformers authored these tensors and their names)
    batch = np.asarray([ids], np.int64)
    with torch.no_grad():
        want = text_model(torch.from_numpy(batch)).last_hidden_state.numpy()
    got, _ = comps.text_encoders[0].apply(
        jax.tree.map(lambda a: np.asarray(a, np.float32),
                     comps.params["text_encoder_0"]),
        batch.astype(np.int32))
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               atol=2e-2, rtol=2e-2)  # bf16 params

    # ---- jitted render -> artifact envelope (the executor's own path) --
    pool = ChipPool(n_slots=1, mesh_spec=MeshSpec({"data": 1}),
                    devices=jax.devices()[:1])
    job = {"id": "e2e-1", "model_name": name, "prompt": "hi cat",
           "seed": 7, "num_inference_steps": 2, "height": 256,
           "width": 256, "content_type": "image/jpeg"}
    result = synchronous_do_work(job, pool.slots[0], registry)

    cfg = result["pipeline_config"]
    assert "error" not in cfg, cfg
    art = result["artifacts"]["primary"]
    assert art["content_type"] == "image/jpeg"
    assert art["blob"] and art["thumbnail"] and art["sha256_hash"]
    assert cfg["model_name"] == name and cfg["seed"] == 7

    # determinism: the same job renders byte-identical artifacts
    again = synchronous_do_work(dict(job), pool.slots[0], registry)
    assert again["artifacts"]["primary"]["sha256_hash"] == art["sha256_hash"]
