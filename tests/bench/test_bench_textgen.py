"""The ``textgen`` kind (``perfbench/kinds/textgen.py``) at the small
size on the CPU: its configuration and mix files, its two copies of the
plain reference, a run through ``cell.run_cell`` as
``test_bench_kind.py`` makes for the caption kind, the control, and the
two readers and seven metric files that came with it."""

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
sys.path.insert(0, str(ROOT / "tests"))

from perfbench import cell, kinds, readers, textref, traffic  # noqa: E402
from perfbench.kinds import textgen  # noqa: E402
from perfbench.readers import counter_ratio  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "ling-3.0-flash-vl.sample32"
BIG = json.loads((ROOT / "perfbench" / "configs"
                  / "ling-3.0-flash-vl.json").read_text())
TINY = json.loads((HERE / "configs" / "ling-tiny-cut.json").read_text())
MIX = json.loads((HERE / "traffic" / "sample-tiny.json").read_text())
NEW = ["text_prefill_s.lat", "text_decode_s.lat", "prefill_device_ms.lat",
       "decode_device_ms.lat", "moe_tokens_per_expert.lat",
       "decode_hbm_roofline.lat", "moe_experts_hit.lat"]
TINY_CELL = "ling-tiny-cut.sample-tiny"
TINY_BENCH = {
    "end_to_end": [
        {"name": "job_p50_s", "unit": "s", "workloads": [TINY_CELL]},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": [
        {"name": m["name"], "unit": m["unit"], "workloads": [TINY_CELL]}
        for m in BENCH["per_layer"] if CELL in m["workloads"]]}


# ---- the files -----------------------------------------------------------


def test_the_cell_is_what_the_issue_names():
    cell_entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell_entry["chips"] == 1 and cell_entry["traffic"] == "sample32"
    mix = traffic.load_mix("sample32")
    assert mix["clients"] == 1 and mix[textgen.UNIT] == [[[16384, 128, 32],
                                                          1.0]]
    assert mix["warm_solo"] == [[[16384, 128, 32], 1]]
    textgen.check_mix(mix)
    serving = BIG["serving"]
    assert serving["temperature"] == 1.0 and serving["logprobs"] is True
    assert serving["prefill_chunk"] == 2048 and serving["dtype"] == "bfloat16"
    mine = [m["name"] for m in BENCH["per_layer"] if CELL in m["workloads"]]
    assert mine == ["hive_queue_s.lat", "upload_s.lat", "admission_s.lat",
                    "step_mfu.lat", "device_idle_pct.lat"] + NEW
    # the new entries come after the accepted ones (a later PR appends
    # its own after these), each for this cell alone
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(NEW[0])
    assert at >= 15 and names[at:at + len(NEW)] == NEW
    for m in BENCH["per_layer"][at:at + len(NEW)]:
        assert m["workloads"] == [CELL] and m["moves"] == "job_p50_s"
        assert m["layer"] == "text programs"


def test_the_configuration_keeps_every_published_width():
    """Every key of the catalog row as published but the three of
    ``reduced``, each with its published value and the deployment
    beside it (``test_bench_files.py`` holds the entry to the same)."""
    published = {"hidden_size": 2560, "intermediate_size": 6144,
                 "moe_intermediate_size": 768, "num_experts_per_tok": 8,
                 "num_attention_heads": 32, "kv_lora_rank": 512,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "v_head_dim": 128, "head_dim": 128, "n_group": 8,
                 "topk_group": 4, "layer_group_size": 6,
                 "first_k_dense_replace": 2, "short_conv_kernel_size": 4,
                 "kda_lower_bound": -5, "rope_theta": 6000000,
                 "routed_scaling_factor": 2.5, "q_lora_rank": None,
                 "moe_shared_expert_intermediate_size": 768,
                 "max_position_embeddings": 131072}
    for key, value in published.items():
        assert BIG[key] == value, key
    assert BIG["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert BIG["published"] == {"num_hidden_layers": 42,
                                "num_experts": 512, "vocab_size": 157184}
    assert (BIG["num_hidden_layers"], BIG["num_experts"],
            BIG["vocab_size"]) == (8, 128, 39296)
    assert BIG["deployment"]["chips_sharing_a_layer"] == 4
    assert BIG["experts_held"] == [0, 128]
    # the floors of the model-configs guide
    assert BIG["num_hidden_layers"] - BIG["first_k_dense_replace"] >= 4
    assert BIG["num_experts"] >= 8 and BIG["vocab_size"] * 8 >= 157184
    assert len(BIG["assumed"]) >= 8 and len(BIG["left_out"]) == 2
    cfg = textgen.ling_config(BIG)
    assert cfg.num_experts == 512 and cfg.experts_held == (0, 128)
    assert cfg.mla_layers == [5] and cfg.kda_chunk == 64
    assert textref.sizes(BIG)["router_outputs"] == 512


@pytest.mark.parametrize("config", [BIG, TINY], ids=lambda c: c["name"])
def test_check_config_and_the_cut(config):
    textgen.check_config(config)
    assert kinds.of(config) is textgen
    assert set(config["published"]) == set(config["reduced"])
    for key in config["reduced"]:
        assert config[key] != config["published"][key]
    assert config["deployment"]["chips_sharing_a_layer"] == 4
    broken = dict(config, experts_held=[0, config["num_experts"] + 1])
    with pytest.raises(AssertionError):
        textgen.check_config(broken)


def test_check_mix_wants_every_shape_warmed():
    textgen.check_mix(MIX)
    with pytest.raises(AssertionError):
        textgen.check_mix(dict(MIX, warm_solo=MIX["warm_solo"][:1]))
    with pytest.raises(AssertionError):
        textgen.check_mix(dict(MIX, tokens=[[[8, 4, 1], 1.0]]))


@pytest.mark.parametrize("name", NEW)
def test_new_metric_file_names_what_it_reads(name):
    spec = json.loads((ROOT / "perfbench" / "metrics"
                       / f"{name}.json").read_text())
    args = spec["args"]
    sources = args.get("spans") or [args.get("program")
                                    or args["numerator"]["family"]]
    for source in sources:
        assert source in spec["what"], (name, source)


# ---- jobs ----------------------------------------------------------------


def test_every_seed_gets_the_same_units_and_its_own_prompts():
    for seed in (0, 7, 2 ** 31 + 12345):
        units = traffic.units(MIX, textgen.UNIT, 20, seed)
        assert sorted(map(tuple, units[:10])) \
            == [(8, 4, 2)] * 5 + [(19, 6, 3)] * 5
    a = traffic.make_job(textgen, 3, [19, 6, 3], 5, TINY, "m")
    assert a == traffic.make_job(textgen, 3, [19, 6, 3], 5, TINY, "m")
    assert a != traffic.make_job(textgen, 3, [19, 6, 3], 6, TINY, "m")
    assert a["workflow"] == "txt2txt" and a["logprobs"] is True
    assert (a["max_new_tokens"], a["num_return_sequences"]) == (6, 3)
    ids = textgen.ids_of(a["prompt"], TINY)
    assert len(ids) == 19 and all(0 <= i < 96 for i in ids)
    assert textgen.job_size(a) == 19 + 3 * 6
    assert textgen.ids_of("aa zz", TINY) is None       # 675 is past 95
    assert textgen.ids_of("aa b1", TINY) is None
    assert traffic.unit_label([16384, 128, 32]) == "16384_128_32"
    big = traffic.make_job(textgen, 0, [16384, 128, 32], 2 ** 31 + 5, BIG,
                           "m")
    words = big["prompt"].split()
    assert len(words) == 16384 and all(len(w) == 4 for w in words)
    assert max(textgen.ids_of(big["prompt"], BIG)) < 39296


# ---- the two copies of the reference -------------------------------------


@pytest.fixture(scope="module")
def tiny_params():
    return textgen.seeded_params(TINY, 2 ** 31 + 7, None)


def test_the_two_copies_of_the_reference_agree(tiny_params):
    """``perfbench/textref.py`` and ``tests/ling_reference.py`` share no
    code; float32 both, so they differ by rounding (logits ~3: 1e-4 is
    ten times what they read apart)."""
    import ling_reference

    ids = np.random.RandomState(3).randint(0, 96, 23)
    c = textref.sizes(TINY)
    mine = np.asarray(textref.forward(tiny_params, c, ids))
    theirs = np.asarray(ling_reference.forward(tiny_params, c, ids))
    assert mine.shape == (23, 96)
    assert np.abs(mine - theirs).max() < 1e-4
    assert np.abs(mine).max() > 0.5


def test_one_pass_over_shared_rows_is_the_pass_over_each(tiny_params):
    c = textref.sizes(TINY)
    rng = np.random.RandomState(4)
    prompt, rows = rng.randint(0, 96, 13), rng.randint(0, 96, (2, 5))
    tree = np.asarray(textref.forward_tree(tiny_params, c, prompt, rows))
    for r in range(2):
        whole = np.asarray(textref.forward(
            tiny_params, c, np.concatenate([prompt, rows[r]])))
        assert np.abs(tree[r] - whole[12:17]).max() < 1e-4
    logprobs = textref.token_logprobs(tree, rows)
    assert logprobs.shape == (2, 5) and (logprobs < 0).all()
    # over the whole slice the probabilities at one position sum to one
    everywhere = textref.token_logprobs(
        np.repeat(tree[0, :1], 96, axis=0), np.arange(96))
    assert np.isclose(np.exp(everywhere).sum(), 1.0, atol=1e-6)


def test_the_seeded_weights_are_the_layout_at_the_kinds_scales(tiny_params):
    layer = tiny_params["layers"][2]
    assert layer["mlp"]["experts"]["gate"].shape == (4, 64, 32)
    assert layer["mlp"]["router"].shape == (64, 16)
    assert tiny_params["embed"].shape == (96, 64)
    assert np.allclose(np.asarray(layer["attn_norm"]), 1.0)
    bias = np.asarray(layer["attn"]["dt_bias"])
    assert -6.6 < bias.min() < bias.max() < -3.4
    # a stacked expert kernel is scaled by its own fan-in (64), not by
    # experts x fan-in
    std = float(np.asarray(layer["mlp"]["experts"]["gate"]).std())
    assert 0.8 * 64 ** -0.5 < std < 1.2 * 64 ** -0.5
    again = textgen.seeded_params(TINY, 2 ** 31 + 7, None)
    other = textgen.seeded_params(TINY, 2 ** 31 + 8, None)
    assert np.array_equal(np.asarray(again["head"]),
                          np.asarray(tiny_params["head"]))
    assert not np.array_equal(np.asarray(other["head"]),
                              np.asarray(tiny_params["head"]))


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
    result = drive(monkeypatch, trace=False, seed=2 ** 31 + 51)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
    assert set(result["metrics"]) == {"job_p50_s", "setup_s"}
    assert result["attempted"] >= 3 and result["failed"] == 0
    compared = result["compared"]
    assert set(compared) == {"logprob_gap", "logprob_gap_median",
                             "programs_compiled_in_window"}
    gap, median = compared["logprob_gap"], compared["logprob_gap_median"]
    assert gap["limit"] == TINY["compare"]["logprob_gap_limit"]
    assert median["limit"] == TINY["compare"]["logprob_gap_median_limit"]
    # float32 program against float32 reference: rounding and no more
    assert 0 <= median["value"] <= gap["value"] < 0.1 * gap["limit"]
    assert result["correct"] is (
        compared["programs_compiled_in_window"]["value"] == 0)
    json.dumps(result)


def test_traced_run_reports_the_counter_metric_and_the_host_phases(
        monkeypatch):
    """Off the chip the span, program and roofline readers stay silent
    (``spans.on_chip``); the counter's ratio and the flight record's
    phases are read anywhere."""
    result = drive(monkeypatch, trace=True, seed=2 ** 31 + 52)
    assert set(result["metrics"]) == {
        "hive_queue_s.lat", "upload_s.lat", "admission_s.lat",
        "moe_tokens_per_expert.lat", "moe_experts_hit.lat"}
    per_expert = result["metrics"]["moe_tokens_per_expert.lat"]["value"]
    assert 1.0 <= per_expert <= 3.0     # at most the rows of a job
    # of the 4 experts held, those a layer reads in a step
    assert 0.0 < result["metrics"]["moe_experts_hit.lat"]["value"] <= 4.0
    assert result["compared"]["logprob_gap"]["value"] \
        <= result["compared"]["logprob_gap"]["limit"]


def test_a_logprob_altered_where_it_is_served_is_not_correct(monkeypatch):
    from chiaswarm_tpu.pipelines import text as text_mod

    real = text_mod.TextPipeline.__call__

    def altered(self, prompt, **kwargs):
        out = real(self, prompt, **kwargs)
        out["sequences"][-1]["token_logprobs"][2] += 0.01
        return out

    monkeypatch.setattr(text_mod.TextPipeline, "__call__", altered)
    result = drive(monkeypatch, trace=False, seed=2 ** 31 + 53)
    gap = result["compared"]["logprob_gap"]
    assert result["failed"] == 0 and gap["value"] > 5 * gap["limit"]
    assert result["correct"] is False


def test_the_control_one_precision_down_is_not_correct():
    workload = {"name": TINY_CELL, "config": TINY["name"],
                "traffic": "sample-tiny", "chips": 1}
    result = cell.run_control(workload=workload, config=TINY, mix=MIX,
                              seed=2 ** 31 + 54, n_jobs=3,
                              require_tpu=False)
    assert result["control"] == "bfloat16" and result["attempted"] == 3
    gap = result["compared"]["logprob_gap"]
    median = result["compared"]["logprob_gap_median"]
    assert gap["value"] > 3 * gap["limit"]
    assert median["value"] > 3 * median["limit"]
    assert result["correct"] is False


# ---- the work of a job, and the readers ----------------------------------


def test_job_flops_and_decode_bytes_at_the_cells_size():
    job = traffic.make_job(textgen, 0, [16384, 128, 32], 1, BIG, "m")
    flops = textgen.job_flops(BIG, job)
    # ~25 TFLOP of prefill (16k tokens x ~1.4 G a token + attention) and
    # ~6 of decode
    assert 25e12 < flops < 40e12
    shorter = dict(job, max_new_tokens=64)
    assert textgen.job_flops(BIG, shorter) < flops
    # a step: ~1.6 GB outside the experts, 0.94 GB of state, latents; and
    # 11.8 MB for each expert hit
    none_hit = textgen.decode_bytes(BIG, job, 0.0)
    assert 2.4e9 < none_hit / 127 < 3.2e9
    expert = 3 * 2560 * 768 * 2
    assert textgen.decode_bytes(BIG, job, 127 * 6 * 50.0) - none_hit \
        == pytest.approx(127 * 6 * 50 * expert)
    assert textgen.kernel_sites(BIG) == []


def fake_context(registry_before, registry_after, config=BIG, traced=None):
    job = traffic.make_job(textgen, 0, [64, 8, 4], 1, BIG, "m")
    return readers.Context(
        workload={"name": CELL}, config=config, mix={}, latencies=[1.0],
        ran={"before": {"registry": registry_before, "stepper": {}},
             "after": {"registry": registry_after, "stepper": {}},
             "traced": traced, "sent": {"a": {"job": job}}},
        good=[{"id": "a", "record": {}}], window_s=10.0,
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        capture=None)


def test_the_new_readers_find_nothing_on_a_program_without_the_counters():
    """The parent commit has neither counter nor span: the readers
    return None and do not raise, and the line leaves the metrics out."""
    parent = fake_context({}, {"chiaswarm_compiles_total": {"values": {}}})
    for name in NEW:
        assert readers.read(name, parent) is None, name
    # a diffusion cell's kind has no decode_bytes: silent there too
    sdxl = json.loads((ROOT / "perfbench" / "configs"
                       / "sdxl-1024.json").read_text())
    other = fake_context({}, {}, config=sdxl, traced={"dir": "/nowhere",
                                                      "window_s": 5.0})
    assert readers.read("decode_hbm_roofline.lat", other) is None


def test_counter_ratio_reads_the_labelled_series():
    pairs, hit = ("chiaswarm_moe_routed_pairs_total",
                  "chiaswarm_moe_experts_hit_total")
    before = {pairs: {"values": {"decode,yes": 10.0, "prefill,yes": 99.0}},
              hit: {"values": {"": 4.0}}}
    after = {pairs: {"values": {"decode,yes": 70.0, "prefill,yes": 500.0,
                                "decode,no": 180.0}},
             hit: {"values": {"": 52.0}}}
    context = fake_context(before, after)
    assert counter_ratio.delta(context, pairs, "decode,no") == 180.0
    assert readers.read("moe_tokens_per_expert.lat", context) \
        == pytest.approx(60.0 / 48.0)
    # no layer-steps family in this snapshot: that metric is left out
    assert readers.read("moe_experts_hit.lat", context) is None
    steps = "chiaswarm_moe_layer_steps_total"
    counted = fake_context(dict(before, **{steps: {"values": {"": 6.0}}}),
                           dict(after, **{steps: {"values": {"": 18.0}}}))
    assert readers.read("moe_experts_hit.lat", counted) \
        == pytest.approx(48.0 / 12.0)
    still = fake_context(after, after)
    assert readers.read("moe_tokens_per_expert.lat", still) is None


def test_decode_hbm_roofline_is_bytes_over_device_time(monkeypatch):
    from perfbench import programs

    hit = "chiaswarm_moe_experts_hit_total"
    context = fake_context({hit: {"values": {"": 0.0}}},
                           {hit: {"values": {"": 40.0}}},
                           traced={"dir": "x", "window_s": 5.0})
    monkeypatch.setattr(programs, "load", lambda d, w: {
        "window_s": w, "modules": [["jit_text_decode(1)", 0, 4_000_000],
                                   ["jit_text_decode(1)", 0, 6_000_000],
                                   ["jit_text_prefill(2)", 0, 1_000_000]]})
    job = context.ran["sent"]["a"]["job"]
    want = 100.0 * textgen.decode_bytes(BIG, job, 40.0) / (5e-3 * 819e9)
    assert readers.read("decode_hbm_roofline.lat", context) \
        == pytest.approx(want)
    assert readers.read("decode_device_ms.lat", context) \
        == pytest.approx(5.0)
    assert readers.read("prefill_device_ms.lat", context) \
        == pytest.approx(1.0)
