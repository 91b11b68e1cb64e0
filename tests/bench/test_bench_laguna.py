"""The ``textgen_laguna`` kind (``perfbench/kinds/textgen_laguna.py``) at
the small size on the CPU: its configuration files, its two copies of
the plain reference, a run through ``cell.run_cell``, the control, the
work of a job by layer type against hand counts and brute-force counts,
and the reader and three metric files that came with it."""

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

from perfbench import cell, kinds, lagunaref, readers, traffic  # noqa: E402
from perfbench.kinds import textgen_laguna as kind  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "laguna-xs.2.sample32"
BIG = json.loads((ROOT / "perfbench" / "configs"
                  / "laguna-xs.2.json").read_text())
TINY = json.loads((HERE / "configs" / "laguna-tiny-cut.json").read_text())
MIX = json.loads((HERE / "traffic" / "sample-tiny.json").read_text())
NEW = ["window_flash_attention_roofline.lat", "window_pairs_scored_x.lat",
       "shared_prompt_attention_roofline.lat"]
LISTED = ["hive_queue_s.lat", "upload_s.lat", "admission_s.lat",
          "step_mfu.lat", "device_idle_pct.lat", "text_prefill_s.lat",
          "text_decode_s.lat", "moe_tokens_per_expert.lat",
          "moe_experts_hit.lat", "prefill_whole_ms.lat",
          "decode_whole_ms.lat", "decode_roofline.lat",
          "causal_flash_attention_roofline.lat"]
TINY_CELL = "laguna-tiny-cut.sample-tiny"
TINY_BENCH = {
    "end_to_end": [
        {"name": "job_p50_s", "unit": "s", "workloads": [TINY_CELL]},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": [
        {"name": m["name"], "unit": m["unit"], "workloads": [TINY_CELL]}
        for m in BENCH["per_layer"] if CELL in m["workloads"]]}
PERIOD = ["full_attention"] + ["sliding_attention"] * 3


# ---- the files -----------------------------------------------------------


def test_the_cell_is_what_the_issue_names():
    """The cell and its metrics ARE in the benchmark's lists (never that
    they are last: the next cell would turn such a test red)."""
    cell_entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell_entry["chips"] == 1 and cell_entry["traffic"] == "sample32"
    assert cell_entry["config"] == "laguna-xs.2"
    entry = next(c for c in BENCH["configs"] if c["name"] == "laguna-xs.2")
    assert entry["file"] == "perfbench/configs/laguna-xs.2.json"
    assert entry["source"] == BIG["source"] \
        == "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json"
    mix = traffic.load_mix("sample32")
    assert mix["clients"] == 1 and mix["warm_burst"] == []
    assert mix[kind.UNIT] == [[[16384, 128, 32], 1.0]]
    assert mix["warm_solo"] == [[[16384, 128, 32], 1]]
    kind.check_mix(mix)
    serving = BIG["serving"]
    assert serving["temperature"] == 1.0 and serving["logprobs"] is True
    assert serving["prefill_chunk"] == 2048 and serving["dtype"] == "bfloat16"
    assert serving["max_context"] == 16384
    assert serving["router_dtype"] == "float32"
    assert serving["residency_budget_fraction"] == 0.75
    mine = [m["name"] for m in BENCH["per_layer"] if CELL in m["workloads"]]
    assert sorted(mine) == sorted(LISTED + NEW)
    # the three that average cut events, and the diffusion kernels' share,
    # do not list this cell (PERF.md 5b; ROADMAP R3d)
    for name in ("prefill_device_ms.lat", "decode_device_ms.lat",
                 "decode_hbm_roofline.lat", "flash_roofline.lat"):
        assert name not in mine
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "job_p50_s"
    assert by_name[NEW[0]]["layer"] == by_name[NEW[2]]["layer"] == "kernels"
    assert by_name[NEW[0]]["source"] == "device_trace"
    assert by_name[NEW[1]]["layer"] == "text programs"
    assert (by_name[NEW[1]]["source"], by_name[NEW[1]]["unit"],
            by_name[NEW[1]]["better"]) == ("program_counter", "x", "lower")
    p50 = next(m for m in BENCH["end_to_end"] if m["name"] == "job_p50_s")
    assert CELL in p50["workloads"] and p50["bound"] == 0.02
    assert all(w["chips"] == 1 for w in BENCH["workloads"])


def test_the_configuration_keeps_every_published_width():
    """Every key of the catalog row as published but the depth and the
    three per-layer lists that follow it, each with its published value
    and the deployment beside it (``test_bench_files.py`` holds the entry
    to the same)."""
    published = {
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
        "intermediate_size": 8192, "num_attention_heads": 48,
        "num_key_value_heads": 8, "head_dim": 128,
        "max_position_embeddings": 262144, "attention_bias": False,
        "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
        "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
        "moe_apply_router_weight_on_input": False,
        "partial_rotary_factor": 0.5, "moe_routed_scaling_factor": 2.5,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                "original_max_position_embeddings": 4096, "beta_slow": 1,
                "beta_fast": 64, "attention_factor": 1.4158883083359672,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {
                "rope_type": "default", "rope_theta": 10000,
                "partial_rotary_factor": 1},
            "original_max_position_embeddings": 4096}}
    for key, value in published.items():
        assert BIG[key] == value, key
    assert BIG["reduced"] == ["num_hidden_layers", "layer_types",
                              "mlp_layer_types",
                              "num_attention_heads_per_layer"]
    assert BIG["published"] == {
        "num_hidden_layers": 40, "layer_types": PERIOD * 10,
        "mlp_layer_types": ["dense"] + ["sparse"] * 39,
        "num_attention_heads_per_layer": [48, 64, 64, 64] * 10}
    assert BIG["num_hidden_layers"] == 7
    assert BIG["layer_types"] == (PERIOD * 2)[:7]
    assert BIG["num_attention_heads_per_layer"] \
        == [48, 64, 64, 64, 48, 64, 64]
    assert BIG["mlp_layer_types"] == ["dense"] + ["sparse"] * 6
    assert BIG["deployment"]["chips_sharing_a_layer"] == 1
    assert BIG["experts_held"] == [0, 256]
    # the floors of the model-configs guide: a whole period and at least
    # four of the layers behind the leading dense one, every expert, the
    # whole vocabulary
    assert BIG["layer_types"][:4] == PERIOD
    assert BIG["mlp_layer_types"].count("sparse") >= 4
    assert len(BIG["assumed"]) >= 10 and len(BIG["left_out"]) == 1
    cfg = kind.laguna_config(BIG)
    assert cfg.num_experts == 256 and cfg.experts_held == (0, 256)
    assert cfg.stack == "laguna" and cfg.vocab_size == 100352
    assert cfg.rope_parameters.full_attention.factor == 64
    assert cfg.rope_parameters.sliding_attention.rope_type == "default"
    assert cfg.window(0) is None and cfg.window(1) == 512
    assert lagunaref.sizes(BIG)["router_outputs"] == 256


def test_the_resident_arguments_are_what_the_issue_reckons():
    """11.1-11.2 GB of weights at two bytes a parameter (the routers
    float32): layer 0 + six expert layers + embedding and head."""
    import jax

    from chiaswarm_tpu.models import laguna

    shapes = laguna.param_shapes(kind.laguna_config(BIG))
    resident = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                   for s in jax.tree.leaves(shapes))
    assert 11.1e9 < resident < 11.2e9
    assert resident < 0.75 * 16.9e9          # the registry's budget
    w = kind._weights(BIG)
    assert w["expert"] == 3 * 2048 * 512 == 3_145_728
    assert w["attention"] == 2 * 29_458_432 + 5 * 37_879_808
    assert w["dense_mlp"] == 50_331_648 and w["head"] == 2048 * 100352


@pytest.mark.parametrize("config", [BIG, TINY], ids=lambda c: c["name"])
def test_check_config_and_the_cut(config):
    kind.check_config(config)
    assert kinds.of(config) is kind
    assert set(config["published"]) == set(config["reduced"])
    for key in config["reduced"]:
        assert config[key] != config["published"][key]
    assert config["deployment"]["chips_sharing_a_layer"] == 1
    n = config["num_hidden_layers"]
    with pytest.raises(AssertionError):     # a list that lost an entry
        kind.check_config(dict(config, layer_types=config[
            "layer_types"][:n - 1]))
    with pytest.raises(AssertionError):     # not the published list's start
        kind.check_config(dict(config, num_attention_heads_per_layer=config[
            "num_attention_heads_per_layer"][::-1]))
    with pytest.raises(AssertionError):
        kind.check_config(dict(config, gating=False))
    with pytest.raises(AssertionError):     # an expert count that was cut
        kind.check_config(dict(config, experts_held=[0, 1 + config[
            "num_experts"]]))


@pytest.mark.parametrize("name", NEW)
def test_new_metric_file_names_what_it_reads(name):
    spec = json.loads((ROOT / "perfbench" / "metrics"
                       / f"{name}.json").read_text())
    args = spec["args"]
    if spec["reader"] == "counter_ratio":
        for part in ("numerator", "denominator"):
            assert args[part]["family"] in spec["what"]
            assert args[part]["family"] == "chiaswarm_text_window_pairs_total"
        return
    assert spec["reader"] == "job_kernel_roofline"
    assert args["program"] in spec["what"] and args["kernel"] in spec["what"]
    assert args["kernel"] == name.removesuffix("_roofline.lat")
    for function in ("flops", "bytes"):
        assert args[function] in spec["what"]
        assert callable(getattr(kind, args[function]))
    assert args["counter"]["family"].startswith("chiaswarm_text_")


def test_the_kind_takes_from_textgen_what_does_not_know_the_model():
    from perfbench.kinds import textgen

    for name in ("UNIT", "PROGRAM_MODULES", "word", "ids_of", "job",
                 "job_size", "decode_artifact", "served_rows", "check_mix"):
        assert getattr(kind, name) is getattr(textgen, name), name
    for name in ("seeded_params", "build", "reference_logprobs", "check",
                 "control", "job_flops", "decode_bytes", "kernel_sites",
                 "check_config"):
        assert getattr(kind, name) is not getattr(textgen, name), name
    big = traffic.make_job(kind, 0, [16384, 128, 32], 2 ** 31 + 5, BIG, "m")
    words = big["prompt"].split()
    assert len(words) == 16384 and all(len(w) == 4 for w in words)
    ids = kind.ids_of(big["prompt"], BIG)
    assert max(ids) < 100352 and max(ids) > 25600    # the whole vocabulary
    assert (big["max_new_tokens"], big["num_return_sequences"]) == (128, 32)
    assert kind.kernel_sites(BIG) == []
    for name in ("causal_flash_attention", "window_flash_attention",
                 "shared_prompt_attention"):
        assert name in kind.kernel_sites.__doc__


# ---- the two copies of the reference -------------------------------------


@pytest.fixture(scope="module")
def tiny_params():
    return kind.seeded_params(TINY, 2 ** 31 + 7, None)


def test_the_two_copies_of_the_reference_agree(tiny_params):
    """``perfbench/lagunaref.py`` and ``tests/laguna_reference.py`` share
    no code; float32 both, so they differ by rounding (logits ~3: 1e-4
    is ten times what they read apart)."""
    import laguna_reference

    ids = np.random.RandomState(3).randint(0, 96, 23)
    mine = np.asarray(lagunaref.forward(tiny_params, lagunaref.sizes(TINY),
                                        ids))
    sizes = {**{k: TINY[k] for k in laguna_reference.NAMES},
             "rope_parameters": {
                 t: {**{"factor": 1.0, "beta_fast": 32, "beta_slow": 1,
                        "original_max_position_embeddings": 4096,
                        "attention_factor": 1.0},
                     **TINY["rope_parameters"][t]}
                 for t in ("full_attention", "sliding_attention")}}
    theirs = np.asarray(laguna_reference.forward(tiny_params, sizes, ids))
    assert mine.shape == (23, 96)
    assert np.abs(mine - theirs).max() < 1e-4
    assert np.abs(mine).max() > 0.5


def test_one_pass_over_shared_rows_is_the_pass_over_each(tiny_params):
    """Rows longer than the window: a row's sliding layers lose the
    prompt's keys one by one and then their own oldest."""
    c = lagunaref.sizes(TINY)
    rng = np.random.RandomState(4)
    prompt, rows = rng.randint(0, 96, 13), rng.randint(0, 96, (2, 11))
    tree = np.asarray(lagunaref.forward_tree(tiny_params, c, prompt, rows))
    for r in range(2):
        whole = np.asarray(lagunaref.forward(
            tiny_params, c, np.concatenate([prompt, rows[r]])))
        assert np.abs(tree[r] - whole[12:23]).max() < 1e-4
    logprobs = lagunaref.token_logprobs(tree, rows)
    assert logprobs.shape == (2, 11) and (logprobs < 0).all()


def test_attention_in_query_blocks_is_attention_over_all_queries():
    """The reference scores a block of queries at a time so that 16,384
    keys at 8 heads a group fit; the blocks are independent, so any
    block size gives the same read-out, window or none."""
    rng = np.random.RandomState(5)
    q = rng.randn(21, 3, 16).astype(np.float32)
    k, v = (rng.randn(29, 16).astype(np.float32) for _ in range(2))
    for window in (None, 6):
        outs = [np.asarray(lagunaref.attend(q, k, v, 8, window, 0.25,
                                            "float32", block=block))
                for block in (4, 21, 256)]
        assert np.abs(outs[0] - outs[1]).max() < 1e-5
        assert np.abs(outs[0] - outs[2]).max() < 1e-5
    # the window is a dense mask: the key 6 behind a query is not seen
    far = np.asarray(lagunaref.attend(q, k, v, 8, None, 0.25, "float32"))
    near = np.asarray(lagunaref.attend(q, k, v, 8, 6, 0.25, "float32"))
    assert np.abs(far - near).max() > 1e-2


def test_the_seeded_weights_are_the_layout_at_the_kinds_scales(tiny_params):
    import jax

    layer = tiny_params["layers"][2]
    assert layer["mlp"]["experts"]["gate"].shape == (16, 64, 32)
    assert layer["mlp"]["router"].dtype == np.float32
    assert tiny_params["layers"][0]["attn"]["wq"].shape == (64, 6 * 16)
    assert layer["attn"]["wq"].shape == (64, 4 * 16)
    assert np.all(np.asarray(layer["attn_norm"]) == 1.0)
    embed = np.asarray(tiny_params["embed"], np.float64)
    assert abs(embed.mean() - kind.LEAVES["embed"][0]) < 0.05
    # blind kernels: every column sums to zero over its fan-in
    for name in ("wv", "wg"):
        sums = np.asarray(layer["attn"][name], np.float64).sum(0)
        assert np.abs(sums).max() < 1e-4, name
    assert np.abs(np.asarray(layer["mlp"]["router"],
                             np.float64).sum(0)).max() < 1e-4
    # the shared component: on the rotated columns of every head of wq
    # and wk (a full layer's first 8 of 16, a sliding layer's all),
    # SHARED[layer type] / fan_in x (1 + layer)^0.5 a weight
    for at, rotated in ((0, 8), (2, 16), (4, 8)):
        for name in ("wq", "wk"):
            w = np.asarray(tiny_params["layers"][at]["attn"][name],
                           np.float64)
            column_sum = w.sum(0).reshape(-1, 16)
            want = kind.SHARED[TINY["layer_types"][at]] \
                * (1.0 + at) ** 0.5
            assert np.allclose(column_sum[:, :rotated], want, atol=1e-3)
            assert np.abs(column_sum[:, rotated:]).sum() < 1e-3
    # the gains: wq and wo wider than their fan-in scale, wk not
    attn = tiny_params["layers"][1]["attn"]
    std = {n: float(np.asarray(attn[n], np.float64).std()) for n in attn}
    assert std["wo"] == pytest.approx(kind.GAINS["wo"] * 64 ** -0.5,
                                      rel=0.1)
    assert std["wv"] == pytest.approx(64 ** -0.5, rel=0.1)
    # another seed, other weights; the same seed, the same
    again = kind.seeded_params(TINY, 2 ** 31 + 7, None)
    other = kind.seeded_params(TINY, 2 ** 31 + 8, None)
    same = jax.tree.map(lambda a, b: bool((a == b).all()), tiny_params, again)
    assert all(jax.tree.leaves(same))
    assert not bool((other["head"] == tiny_params["head"]).all())


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
    result = drive(monkeypatch, trace=False, seed=2 ** 31 + 61)
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


def test_traced_run_reports_the_counter_metrics_and_the_host_phases(
        monkeypatch):
    """Off the chip the span, program and roofline readers stay silent
    (``spans.on_chip``); the counters' ratios and the flight record's
    phases are read anywhere, the window's among them."""
    result = drive(monkeypatch, trace=True, seed=2 ** 31 + 62)
    assert set(result["metrics"]) == {
        "hive_queue_s.lat", "upload_s.lat", "admission_s.lat",
        "moe_tokens_per_expert.lat", "moe_experts_hit.lat",
        "window_pairs_scored_x.lat"}
    per_expert = result["metrics"]["moe_tokens_per_expert.lat"]["value"]
    assert 1.0 <= per_expert <= 3.0     # at most the rows of a job
    assert 0.0 < result["metrics"]["moe_experts_hit.lat"]["value"] <= 16.0
    scored = result["metrics"]["window_pairs_scored_x.lat"]
    assert scored["unit"] == "x" and 1.0 <= scored["value"] < 8.0
    assert result["compared"]["logprob_gap"]["value"] \
        <= result["compared"]["logprob_gap"]["limit"]


def test_the_control_one_precision_down_is_not_correct():
    workload = {"name": TINY_CELL, "config": TINY["name"],
                "traffic": "sample-tiny", "chips": 1}
    result = cell.run_control(workload=workload, config=TINY, mix=MIX,
                              seed=2 ** 31 + 64, n_jobs=3,
                              require_tpu=False)
    assert result["control"] == "bfloat16" and result["attempted"] == 3
    gap = result["compared"]["logprob_gap"]
    median = result["compared"]["logprob_gap_median"]
    assert gap["value"] > 3 * gap["limit"]
    assert median["value"] > 3 * median["limit"]
    assert result["correct"] is False


# ---- the work of a job, and the readers ----------------------------------

FULL_ATTN = 2 * 2048 * 48 * 128 + 2 * 2048 * 1024 + 2048 * 48   # 29.46 M
SLIDING_ATTN = 2 * 2048 * 64 * 128 + 2 * 2048 * 1024 + 2048 * 64  # 37.88 M
EXPERT = 3 * 2048 * 512                                 # 3.146 M
DENSE = 3 * 2048 * 8192
OUTSIDE = 2 * FULL_ATTN + 5 * SLIDING_ATTN + DENSE + 6 * EXPERT  # + shared
HEAD = 2048 * 100352


def big_job():
    return traffic.make_job(kind, 0, [16384, 128, 32], 1, BIG, "m")


def test_job_flops_decode_flops_and_decode_bytes_against_hand_counts():
    job = big_job()
    assert FULL_ATTN == 29_458_432 and SLIDING_ATTN == 37_879_808
    # a token: every weight outside the routed experts, the router's 256
    # outputs, and all 8 chosen experts (every one is held), twice
    token = 2.0 * (OUTSIDE + 6 * 2048 * 256 + 6 * 8 * EXPERT)
    assert kind._token_flops(BIG) == token
    assert 16384 * token == pytest.approx(15.46e12, rel=0.01)
    seen_prefill = 5 * (512 * 513 // 2 + (16384 - 512) * 512)
    seen_decode = 5 * 32 * 127 * 512
    assert kind.window_pairs(BIG, job) == (seen_prefill, seen_decode)
    full_decode = 2 * 32 * (127 * 16385 + 127 * 126 // 2)
    pairs = kind.decode_pairs(BIG, job)
    assert pairs == full_decode + seen_decode
    decoded = 32 * 127
    decode = decoded * (token + 2.0 * HEAD) \
        + 4.0 * 128 * (48 * full_decode + 64 * seen_decode)
    assert kind.decode_flops(BIG, job, pairs) == pytest.approx(decode)
    full_prefill = 2 * 16384 * 16385 // 2
    prefill = 16384 * token + 2.0 * HEAD \
        + 4.0 * 128 * (48 * full_prefill + 64 * seen_prefill)
    assert kind.job_flops(BIG, job) == pytest.approx(prefill + decode)
    # the issue's reckoning: the full layers' sweeps 6.6 TFLOP, the
    # windowed 1.35 at key granularity (22.0 were the window ignored),
    # a 23.5 TFLOP prefill
    assert 4.0 * 128 * 48 * full_prefill == pytest.approx(6.6e12, rel=0.01)
    assert 4.0 * 128 * 64 * seen_prefill == pytest.approx(1.35e12, rel=0.01)
    assert 4.0 * 128 * 64 * 5 * 16384 * 16385 / 2 \
        == pytest.approx(22.0e12, rel=0.01)
    assert prefill == pytest.approx(23.5e12, rel=0.01)
    shorter = dict(job, max_new_tokens=64)
    assert kind.job_flops(BIG, shorter) < kind.job_flops(BIG, job)
    # a step: the weights outside the routed experts (the float32 routers
    # and the head included), the two full layers' prompt keys and values
    # once, the five windows, the rows' suffixes; and 6.3 MB an expert hit
    entry = 2 * 8 * 128 * 2
    step = 2 * (OUTSIDE + HEAD) + 4 * 6 * 2048 * 256 \
        + entry * (2 * (16384 + 32 * 64) + 5 * (512 + 32 * 64))
    none_hit = kind.decode_bytes(BIG, job, 0.0)
    assert none_hit == pytest.approx(127 * step)
    assert 1.2e9 < step < 1.3e9
    assert kind.decode_bytes(BIG, job, 127 * 6 * 162.0) - none_hit \
        == pytest.approx(127 * 6 * 162 * 2 * EXPERT)
    # the issue's 7.3 GB a step, 6.1 of it the ~162 experts a layer
    whole = kind.decode_bytes(BIG, job, 127 * 6 * 162.0) / 127
    assert whole == pytest.approx(7.35e9, rel=0.02)


def test_the_kinds_counts_are_the_programs_and_a_brute_force_count():
    """At the tiny size: the pairs inside the window pair by pair, the
    kind's split of the program's counts by layer type, and the two
    against what ``models/laguna.py::job_counts`` feeds the counters."""
    from chiaswarm_tpu.models import laguna

    job = traffic.make_job(kind, 0, [37, 16, 2], 3, TINY, "m")
    cfg = kind.laguna_config(TINY)
    counts = laguna.job_counts(cfg, 37, 2, 16, 16, 64)
    window, sliding = 8, 4
    prefill = sum(1 for p in range(37) for c in range(37)
                  if p - window < c <= p)
    decode = 2 * sum(1 for s in range(15) for c in range(37 + 15)
                     if 37 + s - window < c <= 37 + s)
    assert kind.window_pairs(TINY, job) == (sliding * prefill,
                                            sliding * decode)
    assert counts["window_pairs"]["visible"] == sliding * (prefill + decode)
    assert kind.decode_pairs(TINY, job) == counts["attention_pairs"][1]
    full_prefill = 2 * 37 * 38 // 2
    assert counts["attention_pairs"][0] == full_prefill + sliding * prefill
    # what the readers hand the kind is the program's count over ALL
    # layers; the kind prices the full layers' part at their head count
    assert kind.prefill_attention_flops(
        TINY, job, counts["attention_pairs"][0]) \
        == pytest.approx(4.0 * 16 * 6 * full_prefill)
    assert kind.window_attention_flops(TINY, job) \
        == pytest.approx(4.0 * 16 * 4 * sliding * prefill)
    assert kind.decode_attention_flops(TINY, job) \
        == pytest.approx(4.0 * 16 * 6 * 2 * 2 * 15 * 37)
    # bytes: queries and read-outs once, keys and values a chunk sees
    # once a chunk: ends 16, 32, 37 in a full layer; the window before a
    # chunk and the chunk in a sliding one (16, 8 + 16, 8 + 5)
    assert kind.prefill_attention_bytes(TINY, job) \
        == 2 * 16 * (2 * 37 * 12 + 2 * 2 * 2 * (16 + 32 + 37))
    assert kind.window_attention_bytes(TINY, job) \
        == 2 * 16 * (2 * 37 * 16 + 4 * 2 * 2 * (16 + 24 + 13))
    assert kind.prefill_chunks(TINY, job) == 3


def fake_context(registry_before, registry_after, config=BIG, traced=None):
    return readers.Context(
        workload={"name": CELL}, config=config, mix={}, latencies=[1.0],
        ran={"before": {"registry": registry_before, "stepper": {}},
             "after": {"registry": registry_after, "stepper": {}},
             "traced": traced, "sent": {"a": {"job": big_job()}}},
        good=[{"id": "a", "record": {}}], window_s=10.0,
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        capture=None)


def counted(visible, scored, blocks=0.0):
    return {"chiaswarm_text_window_pairs_total": {
                "values": {"visible": visible, "scored": scored}},
            "chiaswarm_text_decode_key_blocks_total": {
                "values": {"yes": blocks, "no": 0.0}}}


def job_forms(prefill_kernel_ns, decode_kernel_ns):
    """A traced window of a closed loop in the two plain forms: three
    chunks of a job the capture opened in, two whole jobs (eight chunks
    with the small program that adds up their stats between them, then
    the decode), five chunks of one it closed in; in every chunk five
    calls of the windowed kernel and two of the causal one, in every
    decode 2 x 127 calls of the shared-prompt sweep."""
    modules, device, at = [], [], 1_000
    for chunks in (3, 8, 8, 5):
        for _ in range(chunks):
            modules.append(["jit_text_prefill(2)", at, 100_000_000])
            for layer in range(7):
                name = "causal_flash_attention" if layer in (0, 4) \
                    else "window_flash_attention"
                device.append([f"%{name}.{layer} = bf16[1,16384,1024]{{2,1,0}}"
                               " custom-call(s32[1]{0} %p)",
                               at + layer * 12_000_000, prefill_kernel_ns])
            modules.append(["jit_add(3)", at + 100_000_010, 900])
            at += 100_010_000
        modules.append(["jit_text_decode(1)", at, 1_500_000_000])
        for call in range(254):
            device.append(["%shared_prompt_attention.1 = (f32[1,192,1024]"
                           "{2,1,0}, f32[1,192,1024]{2,1,0}) custom-call("
                           "s32[1]{0} %p)", at + call * 5_000_000,
                           decode_kernel_ns])
        at += 1_500_000_100
    del modules[-1:]     # the capture closed inside the last job's decode
    return ({"window_s": 9.0, "modules": modules},
            {"window_s": 9.0, "device": device, "host": []})


@pytest.mark.parametrize("name, calls", [(NEW[0], 40), (NEW[2], 254)])
def test_a_kernels_roofline_over_a_jobs_calls_cannot_pass_100(
        monkeypatch, name, calls):
    """Two whole jobs in the window. A kernel that takes exactly the
    larger of its two bounds over a job's calls reads 100, one twice as
    slow 50; the windowed sweep is bound by operations at the visible
    pairs, the shared-prompt sweep by the prompt's bytes."""
    from perfbench import programs, trace

    spec = json.loads((ROOT / "perfbench" / "metrics"
                       / f"{name}.json").read_text())["args"]
    job = big_job()
    flops_s = getattr(kind, spec["flops"])(BIG, job) / 197e12
    bytes_s = getattr(kind, spec["bytes"])(BIG, job) / 819e9
    if name == NEW[0]:
        assert flops_s > bytes_s        # 6.9 ms of operations a job
        assert 6.5e-3 < flops_s < 7.2e-3
    else:
        assert bytes_s > flops_s        # 21 ms of keys and values a job
        assert 20e-3 < bytes_s < 22e-3
    call_ns = max(flops_s, bytes_s) * 1e9 / calls
    after = counted(5.0e7, 9.0e7, 2032.0)
    for slow, share in ((1, 100.0), (2, 50.0)):
        ns = round(slow * call_ns)
        forms = job_forms(ns, ns)
        monkeypatch.setattr(programs, "load", lambda d, w: forms[0])
        monkeypatch.setattr(trace, "load", lambda d, w: forms[1])
        context = fake_context(counted(0.0, 0.0), after,
                               traced={"dir": "x", "window_s": 9.0})
        assert readers.read(name, context) == pytest.approx(share, rel=1e-4)
    # no whole job in the window, no trace, or a program whose counter
    # did not move (the parent's): nothing to read
    cut = {"window_s": 9.0, "modules": forms[0]["modules"][:5]}
    monkeypatch.setattr(programs, "load", lambda d, w: cut)
    context = fake_context(counted(0.0, 0.0), after,
                           traced={"dir": "x", "window_s": 9.0})
    assert readers.read(name, context) is None
    assert readers.read(name, fake_context(counted(0.0, 0.0), after)) is None
    monkeypatch.setattr(programs, "load", lambda d, w: forms[0])
    still = fake_context(counted(0.0, 0.0), counted(0.0, 0.0),
                         traced={"dir": "x", "window_s": 9.0})
    assert readers.read(name, still) is None


def test_window_pairs_scored_x_is_the_counters_ratio():
    context = fake_context(counted(1.0e7, 2.0e7), counted(6.0e7, 11.0e7))
    assert readers.read("window_pairs_scored_x.lat", context) \
        == pytest.approx(9.0 / 5.0)
    # the cell's job by the program's own host counts: the kernel steps
    # two or three 512-key blocks for a query block's 639 visible keys
    from chiaswarm_tpu.models import laguna

    counts = laguna.job_counts(kind.laguna_config(BIG), 16384, 32, 128, 2048,
                               16384)["window_pairs"]
    assert 1.0 <= counts["scored"] / counts["visible"] < 8.0


def test_the_new_readers_find_nothing_on_a_program_without_the_counter():
    """The parent commit has no ``chiaswarm_text_window_pairs_total`` and
    its decode feeds no key blocks for this kind's kernel: the readers
    return None and do not raise; a kind without the functions (the
    DeepSeek cell's) is silent."""
    parent = fake_context({}, {}, traced={"dir": "/nowhere",
                                          "window_s": 5.0})
    for name in NEW:
        assert readers.read(name, parent) is None, name
    deepseek = json.loads((ROOT / "perfbench" / "configs"
                           / "deepseek-v2.json").read_text())
    other = fake_context(counted(0.0, 0.0), counted(5e7, 9e7, 100.0),
                         config=deepseek,
                         traced={"dir": "/nowhere", "window_s": 5.0})
    assert readers.read(NEW[0], other) is None
    assert readers.read(NEW[2], other) is None
