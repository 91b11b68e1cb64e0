"""UperNet segmentation tests: HF torch fidelity + seg preprocessor wiring.

The reference's seg mode runs ``openmmlab/upernet-convnext-small``
through transformers (swarm/controlnet/input_processor.py:96-115); these
pin the native port (models/upernet.py) to HF's torch model on tiny
widths and cover the weight-gated preprocessor path with its ADE-palette
output.
"""

from __future__ import annotations

import numpy as np
import pytest

from chiaswarm_tpu.models.upernet import (
    UPERNET_TINY,
    UperNetDetector,
    UperNetSeg,
)


def _hf_tiny():
    torch = pytest.importorskip("torch")
    from transformers import ConvNextConfig, UperNetConfig
    from transformers import UperNetForSemanticSegmentation

    backbone = ConvNextConfig(
        depths=[1, 1, 1, 1], hidden_sizes=[8, 16, 24, 32],
        out_features=["stage1", "stage2", "stage3", "stage4"],
        drop_path_rate=0.0)
    cfg = UperNetConfig(
        backbone_config=backbone, hidden_size=16, pool_scales=[1, 2, 3, 6],
        num_labels=10, use_auxiliary_head=True, auxiliary_in_channels=24)
    torch.manual_seed(0)
    model = UperNetForSemanticSegmentation(cfg).eval()
    sd = model.state_dict()
    gen = torch.Generator().manual_seed(5)
    for key, value in sd.items():
        if value.dtype.is_floating_point and "running" not in key:
            sd[key] = torch.randn(value.shape, generator=gen) * 0.05
        elif key.endswith("running_var"):
            sd[key] = torch.rand(value.shape, generator=gen) + 0.5
        elif key.endswith("running_mean"):
            sd[key] = torch.randn(value.shape, generator=gen) * 0.1
    model.load_state_dict(sd)
    return torch, model


@pytest.mark.slow
def test_upernet_conversion_matches_torch():
    torch, hf = _hf_tiny()
    import jax.numpy as jnp

    from chiaswarm_tpu.convert.torch_to_flax import convert_upernet

    state = {k: v.detach().numpy() for k, v in hf.state_dict().items()}
    params = convert_upernet(state)
    x = np.random.RandomState(1).randn(1, 64, 64, 3).astype(np.float32)
    with torch.no_grad():
        tl = hf(torch.from_numpy(x.transpose(0, 3, 1, 2))).logits
        tseg = tl.argmax(dim=1).numpy().astype(np.uint8)
    fseg = np.asarray(UperNetSeg(UPERNET_TINY).apply(params,
                                                     jnp.asarray(x)))
    assert fseg.shape == tseg.shape
    # argmax maps must agree except where the top-2 logits are within
    # float tolerance of each other
    agree = (fseg == tseg).mean()
    assert agree > 0.99, agree


def test_detector_runs_and_colors_with_ade_palette():
    from chiaswarm_tpu.models.ade_palette import ADE20K_PALETTE

    det = UperNetDetector.random(seed=0)
    img = (np.random.RandomState(0).rand(50, 70, 3) * 255).astype(np.uint8)
    out = det(img)
    assert out.shape == (50, 70, 3) and out.dtype == np.uint8
    palette = {tuple(c) for c in ADE20K_PALETTE}
    colors = {tuple(c) for c in out.reshape(-1, 3)[::17]}
    assert colors <= palette


def test_ade_palette_matches_reference_table():
    from chiaswarm_tpu.models.ade_palette import ADE20K_PALETTE

    assert ADE20K_PALETTE.shape == (151, 3)
    assert tuple(ADE20K_PALETTE[0]) == (0, 0, 0)
    assert tuple(ADE20K_PALETTE[1]) == (120, 120, 120)
    assert tuple(ADE20K_PALETTE[4]) == (80, 50, 50)


def test_seg_preprocessor_uses_upernet_when_present(monkeypatch):
    from PIL import Image

    from chiaswarm_tpu.workloads import controlnet as wl

    monkeypatch.setattr(wl, "_SEG", [UperNetDetector.random(seed=1)])
    out = wl.preprocess_image(Image.new("RGB", (64, 48), (12, 160, 90)),
                              {"type": "seg", "preprocess": True})
    assert np.asarray(out).shape == (48, 64, 3)


def test_seg_preprocessor_falls_back(tmp_path, monkeypatch):
    from PIL import Image

    from chiaswarm_tpu.workloads import controlnet as wl

    monkeypatch.setenv("SDAAS_ROOT", str(tmp_path))
    monkeypatch.setattr(wl, "_SEG", [])
    out = wl.preprocess_image(Image.new("RGB", (64, 48), (12, 160, 90)),
                              {"type": "seg", "preprocess": True})
    assert np.asarray(out).shape == (48, 64, 3)
    assert wl._SEG == [None]
