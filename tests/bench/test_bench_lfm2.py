"""The ``textgen_lfm2`` kind (``perfbench/kinds/textgen_lfm2.py``) at the
small size on the CPU: its configuration files and the cut's arithmetic,
the cell in the benchmark's lists (membership only: never an exact list
or a last position, which the next cell would turn red), the plain
reference, a run through ``cell.run_cell``, the control, the work of a
job against hand counts, and the readers that apply to the cell."""

import json
import os
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT))

from perfbench import cell, kinds, lfm2ref, readers, traffic  # noqa: E402
from perfbench.kinds import textgen  # noqa: E402
from perfbench.kinds import textgen_lfm2 as kind  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "lfm2-8b-a1b.sample32"
BIG = json.loads((ROOT / "perfbench" / "configs"
                  / "lfm2-8b-a1b.json").read_text())
TINY = json.loads((HERE / "configs" / "lfm2-tiny-cut.json").read_text())
MIX = json.loads((HERE / "traffic" / "sample-tiny.json").read_text())
LISTED = ["hive_queue_s.lat", "upload_s.lat", "admission_s.lat",
          "step_mfu.lat", "device_idle_pct.lat", "text_prefill_s.lat",
          "text_decode_s.lat", "moe_tokens_per_expert.lat",
          "moe_experts_hit.lat", "prefill_whole_ms.lat",
          "decode_whole_ms.lat", "decode_roofline.lat",
          "causal_flash_attention_roofline.lat",
          "shared_prompt_attention_roofline.lat",
          "recurrence_prefill_ms.lat", "decode_experts_ms.lat",
          "decode_attention_ms.lat", "prefill_experts_ms.lat",
          "decode_experts_roofline.lat"]
#: the share of the decode's recurrence bytes' bound, which reads over 100
#: here: the compiler streams the conv layers' weights into the core's
#: memory by unscoped asynchronous slices that overlap other work, so the
#: scope's time leaves their reads out
NOT_LISTED = ["recurrence_decode_roofline.lat"]
TINY_CELL = "lfm2-tiny-cut.sample-tiny"
TINY_BENCH = {
    "end_to_end": [
        {"name": "job_p50_s", "unit": "s", "workloads": [TINY_CELL]},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": [
        {"name": m["name"], "unit": m["unit"], "workloads": [TINY_CELL]}
        for m in BENCH["per_layer"] if CELL in m["workloads"]]}
#: the catalog's row (LiquidAI/LFM2-8B-A1B config.json) as published
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}
LAYER_TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv",
               "full_attention", "conv", "conv", "conv", "full_attention",
               "conv", "conv", "conv", "full_attention", "conv", "conv",
               "conv", "full_attention", "conv", "conv", "full_attention",
               "conv", "conv"]


# ---- the files -----------------------------------------------------------


def test_the_cell_and_its_metrics_are_in_the_lists():
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "sample32"
    assert entry["config"] == "lfm2-8b-a1b"
    config = next(c for c in BENCH["configs"] if c["name"] == "lfm2-8b-a1b")
    assert config["file"] == "perfbench/configs/lfm2-8b-a1b.json"
    assert config["reduced"] == ["num_hidden_layers", "layer_types"]
    assert config["source"] == BIG["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json")
    mix = traffic.load_mix("sample32")
    assert mix[kind.UNIT] == [[[16384, 128, 32], 1.0]]
    kind.check_mix(mix)
    p50 = next(m for m in BENCH["end_to_end"] if m["name"] == "job_p50_s")
    assert CELL in p50["workloads"]
    mine = {m["name"] for m in BENCH["per_layer"] if CELL in m["workloads"]}
    assert set(LISTED) <= mine
    assert not set(NOT_LISTED) & mine
    # no end-to-end metric of its own
    assert {m["name"] for m in BENCH["end_to_end"]} == {"job_p50_s",
                                                        "setup_s"}


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog row as published but the two of
    ``reduced``, each with its published value beside it, and the
    two-stage deployment."""
    for key, value in PUBLISHED.items():
        assert BIG[key] == value, key
    assert BIG["published"] == {"num_hidden_layers": 24,
                                "layer_types": LAYER_TYPES}
    assert BIG["num_hidden_layers"] == 14
    assert BIG["layer_types"] == LAYER_TYPES[:14]
    assert BIG["head_dim"] == 64 and BIG["tie_word_embeddings"] is True
    assert BIG["experts_held"] == [0, 32]
    assert BIG["deployment"]["chips"] == 2
    assert BIG["deployment"]["chips_sharing_a_layer"] == 1
    # the floors: the dense layers, a whole period and four layers past
    # the dense ones at least, all 32 experts, all of the vocabulary
    assert BIG["num_hidden_layers"] - BIG["num_dense_layers"] >= 4
    assert "full_attention" in BIG["layer_types"][2:]
    assumed = " ".join(BIG["assumed"])
    for reading in ("tied", "1e-6", "q_layernorm", "no activation",
                    "float32", "base-26"):
        assert reading in assumed, reading
    assert any("128000" in x for x in BIG["left_out"])
    cfg = kind.lfm2_config(BIG)
    assert cfg.stack == "lfm2" and cfg.num_experts == 32
    assert cfg.experts_held == (0, 32) and cfg.head_dim == 64
    assert cfg.attention_layers == [2, 6, 10]
    assert len(cfg.conv_layers) == 11
    assert lfm2ref.sizes(BIG)["router_outputs"] == 32


def test_the_cut_is_the_layouts_arithmetic():
    """One expert 3 x 2048 x 1792 = 11.01 M (22.0 MB), 32 a layer 352.3 M;
    a conv mixer 16.78 M, an attention mixer 10.49 M, the dense SwiGLU
    44.04 M, the tied embedding 134.2 M: 2 x 60.82 M + 9 x 369.1 M + 3 x
    362.8 M + 134.2 M = 4.666 B parameters, 9.34 GB (routers float32),
    55% of the chip's 16.9 GB, under the registry's 75%."""
    import jax

    from chiaswarm_tpu.models import lfm2

    w = kind._weights(BIG)
    assert w["expert"] == 3 * 2048 * 1792 == 11_010_048
    assert w["conv"] + w["conv_taps"] == 16_783_360
    assert w["attention"] == 10_485_760
    assert w["dense_mlp"] == 44_040_192 and w["head"] == 134_217_728
    dense = w["conv"] + w["conv_taps"] + w["dense_mlp"]
    conv_moe = w["conv"] + w["conv_taps"] + w["router"] + 32 * w["expert"]
    attn_moe = w["attention"] + w["router"] + 32 * w["expert"]
    assert 60.8e6 < dense < 60.9e6
    assert 369.0e6 < conv_moe < 369.2e6 and 362.7e6 < attn_moe < 362.9e6
    shapes = lfm2.param_shapes(kind.lfm2_config(BIG))
    count = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    resident = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                   for s in jax.tree.leaves(shapes))
    assert abs(count - (2 * dense + 9 * conv_moe + 3 * attn_moe
                        + w["head"])) < 1e5      # the norms' gains
    assert 4.665e9 < count < 4.668e9
    assert 9.33e9 < resident < 9.34e9
    assert 0.55 < resident / 16.9e9 < 0.56
    assert resident < 0.75 * 16.9e9
    # the second stage: layers 14-23 (four attention, six conv layers)
    rest = 2 * (4 * attn_moe + 6 * conv_moe)
    assert 7.3e9 < rest < 7.4e9


@pytest.mark.parametrize("config", [BIG, TINY], ids=lambda c: c["name"])
def test_check_config_and_the_cut(config):
    kind.check_config(config)
    assert kinds.of(config) is kind
    assert set(config["published"]) == set(config["reduced"])
    for key in config["reduced"]:
        assert config[key] != config["published"][key]
    n = config["num_hidden_layers"]
    for wrong in ({"conv_bias": True}, {"tie_word_embeddings": False},
                  {"use_expert_bias": False}, {"head_dim": 128},
                  {"layer_types": ["conv"] * n},
                  {"layer_types": config["published"]["layer_types"][
                      1:n + 1]},
                  {"experts_held": [0, 1 + config["num_experts"]]}):
        with pytest.raises(AssertionError):
            kind.check_config(dict(config, **wrong))


def test_the_kind_takes_from_textgen_what_does_not_know_the_model():
    for name in ("UNIT", "PROGRAM_MODULES", "word", "ids_of", "job",
                 "job_size", "decode_artifact", "served_rows", "check_mix"):
        assert getattr(kind, name) is getattr(textgen, name), name
    for name in ("check", "control"):
        assert getattr(kind, name).__code__ is getattr(textgen,
                                                       name).__code__
        assert getattr(kind, name).__globals__ is vars(kind)
    for name in ("seeded_params", "build", "reference_logprobs",
                 "job_flops", "decode_bytes", "check_config"):
        assert getattr(kind, name) is not getattr(textgen, name), name
    big = traffic.make_job(kind, 0, [16384, 128, 32], 2 ** 31 + 5, BIG, "m")
    ids = kind.ids_of(big["prompt"], BIG)
    assert len(ids) == 16384 and max(ids) < 65536
    assert kind.kernel_sites(BIG) == []


# ---- the reference -------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_params():
    return kind.seeded_params(TINY, 2 ** 31 + 7, None)


def test_one_pass_over_shared_rows_is_the_pass_over_each(tiny_params):
    """A row's convs start from the prompt's last values of z, its
    attention sees the prompt's keys and its own."""
    c = lfm2ref.sizes(TINY)
    rng = np.random.RandomState(4)
    prompt, rows = rng.randint(0, 96, 13), rng.randint(0, 96, (2, 11))
    tree = np.asarray(lfm2ref.forward_tree(tiny_params, c, prompt, rows))
    for r in range(2):
        whole = np.asarray(lfm2ref.forward(
            tiny_params, c, np.concatenate([prompt, rows[r]])))
        assert np.abs(tree[r] - whole[12:23]).max() < 1e-4
    logprobs = lfm2ref.token_logprobs(tree, rows)
    assert logprobs.shape == (2, 11) and (logprobs < 0).all()


def test_the_seeded_weights_are_the_layout_at_the_kinds_scales(tiny_params):
    import jax

    assert "head" not in tiny_params
    embed = np.asarray(tiny_params["embed"], np.float64)
    assert embed.std() == pytest.approx(128 ** -0.5, rel=0.1)
    attn = tiny_params["layers"][2]["attn"]
    for name in ("q_norm", "k_norm"):
        assert np.allclose(np.asarray(attn[name]), kind.LEAVES[name][0])
    assert float(np.asarray(attn["wo"]).std()) == pytest.approx(
        kind.GAINS["wo"] * 128 ** -0.5, rel=0.15)
    conv = tiny_params["layers"][0]["attn"]["conv"]
    assert conv.shape == (3, 128)
    assert float(np.asarray(conv).std()) == pytest.approx(0.5, rel=0.15)
    mlp = tiny_params["layers"][3]["mlp"]
    assert set(mlp) == {"router", "router_bias", "experts"}
    assert mlp["experts"]["gate"].shape == (16, 128, 32)
    assert float(np.asarray(mlp["router_bias"]).std()) == pytest.approx(
        0.02, rel=0.4)
    assert np.allclose(np.asarray(tiny_params["layers"][0]["attn_norm"]), 1)
    again = kind.seeded_params(TINY, 2 ** 31 + 7, None)
    same = jax.tree.map(lambda a, b: bool((a == b).all()), tiny_params, again)
    assert all(jax.tree.leaves(same))


# ---- a run through the cell ----------------------------------------------


def drive(monkeypatch, *, trace, seed, seconds=1.0):
    monkeypatch.setenv("SWARM_TPU_ROOT", os.environ["SWARM_TPU_ROOT"])
    workload = {"name": TINY_CELL, "config": TINY["name"],
                "traffic": "sample-tiny", "chips": 1}
    return cell.run_cell(
        workload=workload, config=TINY, mix=MIX, benchmark=TINY_BENCH,
        seed=seed, seconds=seconds, trace=trace, t_start=time.monotonic(),
        require_tpu=False, out=sys.stderr)


def test_untraced_run_is_correct_by_logprob_gap(monkeypatch):
    result = drive(monkeypatch, trace=False, seed=2 ** 31 + 81)
    assert set(result["metrics"]) == {"job_p50_s", "setup_s"}
    assert result["attempted"] >= 3 and result["failed"] == 0
    compared = result["compared"]
    gap, median = compared["logprob_gap"], compared["logprob_gap_median"]
    assert gap["limit"] == TINY["compare"]["logprob_gap_limit"]
    # float32 program against float32 reference: rounding and no more
    assert 0 <= median["value"] <= gap["value"] < 0.1 * gap["limit"]
    assert result["correct"] is (
        compared["programs_compiled_in_window"]["value"] == 0)


def test_traced_run_reports_the_counter_metrics_off_the_chip(monkeypatch):
    """Off the chip the scope and program readers stay silent; the
    counters' ratios and the flight record's phases are read anywhere."""
    result = drive(monkeypatch, trace=True, seed=2 ** 31 + 82)
    assert set(result["metrics"]) == {
        "hive_queue_s.lat", "upload_s.lat", "admission_s.lat",
        "moe_tokens_per_expert.lat", "moe_experts_hit.lat"}


def test_the_control_one_precision_down_is_not_correct():
    workload = {"name": TINY_CELL, "config": TINY["name"],
                "traffic": "sample-tiny", "chips": 1}
    result = cell.run_control(workload=workload, config=TINY, mix=MIX,
                              seed=2 ** 31 + 84, n_jobs=3,
                              require_tpu=False)
    assert result["control"] == "bfloat16" and result["attempted"] == 3
    assert result["compared"]["logprob_gap_median"]["value"] \
        > 3 * result["compared"]["logprob_gap_median"]["limit"]
    assert result["correct"] is False


# ---- the work of a job, and the readers ----------------------------------


def big_job():
    return traffic.make_job(kind, 0, [16384, 128, 32], 1, BIG, "m")


CONV = 4 * 2048 * 2048
ATTN = 2 * 2048 * 2048 + 2 * 2048 * 512
DENSE = 3 * 2048 * 7168
EXPERT = 3 * 2048 * 1792
HEAD = 65536 * 2048


def test_job_flops_decode_flops_and_decode_bytes_against_hand_counts():
    job = big_job()
    # a token: every weight outside the experts, the router's 32 outputs,
    # 4 chosen experts of 32 held, the gated conv's 8 operations a
    # channel
    token = 2.0 * (11 * CONV + 3 * ATTN + 2 * DENSE
                   + 12 * (2048 * 32 + 4 * EXPERT)) + 11 * 8 * 2048
    assert kind._token_flops(BIG) == pytest.approx(token)
    prefill_pairs = 3 * 16384 * 16385 // 2
    assert kind.prefill_pairs(BIG, job) == prefill_pairs
    decode_pairs = 3 * 32 * (127 * 16385 + 127 * 126 // 2)
    assert kind.decode_pairs(BIG, job) == decode_pairs
    decode = 32 * 127 * (token + 2.0 * HEAD) + 4.0 * 64 * 32 * decode_pairs
    assert kind.decode_flops(BIG, job, decode_pairs) == pytest.approx(decode)
    prefill = 16384 * token + 4.0 * 64 * 32 * prefill_pairs + 2.0 * HEAD
    assert kind.job_flops(BIG, job) == pytest.approx(prefill + decode)
    # a ~30.6 TFLOP prefill, 57% of it the experts
    assert prefill == pytest.approx(30.6e12, rel=0.02)
    assert 16384 * 2.0 * 12 * 4 * EXPERT / prefill == pytest.approx(
        0.57, abs=0.01)
    # a step: the weights outside the experts once (the tied embedding
    # as the head), the conv tails read and written, the attention
    # layers' keys and values; 22.0 MB an expert
    assert kind.recurrent_state_bytes(BIG, 32) == 11 * 32 * 2 * 2048 * 2
    assert kind.recurrence_weight_bytes(BIG) == 11 * 2 * (CONV + 3 * 2048)
    none_hit = kind.decode_bytes(BIG, job, 0.0)
    hit = 127 * 12 * 31.55
    assert kind.decode_bytes(BIG, job, hit) - none_hit \
        == pytest.approx(hit * 2 * EXPERT)
    # ~9.3 GB a step, ~8.3 of it the experts
    per_step = kind.decode_bytes(BIG, job, hit) / 127
    assert per_step == pytest.approx(9.3e9, rel=0.02)
    assert hit * 2 * EXPERT / 127 == pytest.approx(8.34e9, rel=0.01)
    # 32 x (1 - (28/32)^32) experts hit a layer a step, ~4.06 tokens each
    expected = 32 * (1 - (28 / 32) ** 32)
    assert expected == pytest.approx(31.55, abs=0.01)
    assert 128 / expected == pytest.approx(4.06, abs=0.01)


def test_the_kinds_counts_are_the_programs():
    """The pairs and the conv-tail bytes the kind prices are what
    ``models/lfm2.py::job_counts`` feeds the counters."""
    from chiaswarm_tpu.models import lfm2

    job = big_job()
    counts = lfm2.job_counts(kind.lfm2_config(BIG), 16384, 32, 128, 2048,
                             16384)
    assert counts["attention_pairs"] == (kind.prefill_pairs(BIG, job),
                                         kind.decode_pairs(BIG, job))
    assert counts["recurrent_state_bytes"]["decode"] \
        == 127 * 2 * kind.recurrent_state_bytes(BIG, 32)
    assert kind.prefill_chunks(BIG, job) == 8
    assert counts["expert_layers"] == 12
    # the decode sweep reads the prompt's keys and values once a layer a
    # step: 127 x 3 x 33.6 MB
    assert kind.decode_attention_bytes(BIG, job) > 127 * 3 * 2 * 8 * 64 \
        * 2 * 16384


def fake_context(before, after, config=BIG, job=None):
    return readers.Context(
        workload={"name": CELL}, config=config, mix={},
        latencies=[1.0],
        ran={"before": {"registry": before, "stepper": {}},
             "after": {"registry": after, "stepper": {}},
             "traced": {"dir": "x", "window_s": 9.0},
             "sent": {"a": {"job": job or big_job()}}},
        good=[{"id": "a", "record": {}}], window_s=10.0,
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        capture=None)


def counted(decode):
    return {"chiaswarm_text_recurrent_state_bytes_total": {
        "values": {"prefill": 0.0, "decode": decode}}}


def test_the_recurrence_roofline_reads_the_conv_layers(monkeypatch):
    """The reader's arithmetic over this kind's counts: the conv tails
    the counter moved and the conv layers' weights once a step, over the
    scope's time (a whole decode whose scoped time is exactly those
    bytes' reads 100). The cell is not in the metric's list: on the chip
    the scope's time leaves out the weights' asynchronous reads and the
    share reads over 100 (PERF.md, question 15)."""
    from perfbench.readers import recurrence_roofline, scope_device

    tails = 127 * 2 * kind.recurrent_state_bytes(BIG, 32)
    moved = tails + 127 * kind.recurrence_weight_bytes(BIG)
    least_ns = round(moved / 819e9 * 1e9)
    ops = {"fusion.1": {("bf16", (32, 1, 2048)): frozenset({"recurrence"})},
           "fusion.9": {("bf16", (32, 2048)): frozenset()}}
    modules, device, at = [["jit_text_prefill(2)", 0, 1_000]], [], 10_000
    for _ in range(2):
        modules.append(["jit_text_decode(1)", at, least_ns + 5_000_100])
        device += [["%fusion.1 = bf16[32,1,2048]{2,1,0} fusion(%a)",
                    at + 10, least_ns],
                   ["%fusion.9 = bf16[32,2048]{1,0} fusion(%d)",
                    at + 20 + least_ns, 5_000_000]]
        at += 2 * (least_ns + 5_000_100)
    modules.append(["jit_text_decode(1)", at, 100])
    monkeypatch.setattr(scope_device, "_joined",
                        lambda c: (device, {"jit_text_decode": ops}, modules))
    context = fake_context(counted(0.0), counted(float(tails)))
    assert recurrence_roofline.state_dims(BIG) == (32, 64, 64)
    assert recurrence_roofline.weight_bytes(context) \
        == kind.recurrence_weight_bytes(BIG)
    assert readers.read("recurrence_decode_roofline.lat", context) \
        == pytest.approx(100.0, rel=1e-5)
    # the parent's program counts no state for its layers: silent
    assert readers.read("recurrence_decode_roofline.lat",
                        fake_context({}, {})) is None


def test_the_cells_metrics_find_nothing_off_the_chip():
    parent = readers.Context(
        workload={"name": CELL}, config=BIG, mix={}, latencies=[1.0],
        ran={"before": {"registry": {}, "stepper": {}},
             "after": {"registry": {}, "stepper": {}},
             "traced": {"dir": "/nowhere", "window_s": 5.0},
             "sent": {"a": {"job": big_job()}}},
        good=[{"id": "a", "record": {}}], window_s=10.0,
        device={"platform": "cpu", "kind": "cpu", "count": 1}, capture=None)
    for name in ("recurrence_prefill_ms.lat", "recurrence_decode_roofline.lat",
                 "decode_roofline.lat", "causal_flash_attention_roofline.lat",
                 "shared_prompt_attention_roofline.lat"):
        assert readers.read(name, parent) is None, name
