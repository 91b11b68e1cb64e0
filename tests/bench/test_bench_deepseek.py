"""The ``textgen_deepseek`` kind (``perfbench/kinds/textgen_deepseek.py``)
at the small size on the CPU: its configuration and mix files, its two
copies of the plain reference, a run through ``cell.run_cell``, the
control, the work of a job against hand counts, and the two readers and
three metric files that came with it."""

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

from perfbench import cell, deepseekref, kinds, readers, traffic  # noqa: E402
from perfbench.kinds import textgen_deepseek as kind  # noqa: E402
from perfbench.readers import program_whole  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "deepseek-v2.sample16"
BIG = json.loads((ROOT / "perfbench" / "configs"
                  / "deepseek-v2.json").read_text())
TINY = json.loads((HERE / "configs" / "deepseek-tiny-cut.json").read_text())
MIX = json.loads((HERE / "traffic" / "sample-tiny.json").read_text())
NEW = ["prefill_whole_ms.lat", "decode_whole_ms.lat", "decode_roofline.lat",
       "causal_flash_attention_roofline.lat"]
LING_CELL = "ling-3.0-flash-vl.sample32"
SHARED = ["hive_queue_s.lat", "upload_s.lat", "admission_s.lat",
          "step_mfu.lat", "device_idle_pct.lat", "text_prefill_s.lat",
          "text_decode_s.lat", "moe_tokens_per_expert.lat",
          "moe_experts_hit.lat"]
TINY_CELL = "deepseek-tiny-cut.sample-tiny"
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
    assert cell_entry == BENCH["workloads"][-1]
    assert cell_entry["chips"] == 1 and cell_entry["traffic"] == "sample16"
    assert cell_entry["config"] == "deepseek-v2"
    mix = traffic.load_mix("sample16")
    assert mix["clients"] == 1 and mix["warm_burst"] == []
    assert mix[kind.UNIT] == [[[16384, 64, 16], 1.0]]
    assert mix["warm_solo"] == [[[16384, 64, 16], 1]]
    kind.check_mix(mix)
    serving = BIG["serving"]
    assert serving["temperature"] == 1.0 and serving["logprobs"] is True
    assert serving["prefill_chunk"] == 2048 and serving["dtype"] == "bfloat16"
    assert serving["max_context"] == 16384
    assert serving["residency_budget_fraction"] == 0.75
    mine = [m["name"] for m in BENCH["per_layer"] if CELL in m["workloads"]]
    assert sorted(mine) == sorted(SHARED + NEW)
    # the three of PR 29 that average cut events, and the kernels' share,
    # do not list this cell (PERF.md 5b; ROADMAP R3d)
    for name in ("prefill_device_ms.lat", "decode_device_ms.lat",
                 "decode_hbm_roofline.lat", "flash_roofline.lat"):
        assert name not in mine
    # the new entries are the last of per_layer; the two that read whole
    # executions of a text program read the Ling cell's too (one sound
    # reader on both cells; its three older metrics await a benchmark
    # PR), the two that need this kind's counts are this cell's alone
    assert [m["name"] for m in BENCH["per_layer"][-4:]] == NEW
    for m in BENCH["per_layer"][-4:]:
        whole = m["name"].endswith("_whole_ms.lat")
        assert m["workloads"] == ([LING_CELL, CELL] if whole else [CELL])
        assert m["moves"] == "job_p50_s" and m["source"] == "device_trace"
        assert m["layer"] == ("kernels" if m["name"].startswith("causal")
                              else "text programs")
    p50 = next(m for m in BENCH["end_to_end"] if m["name"] == "job_p50_s")
    assert p50["workloads"][-1] == CELL and p50["bound"] == 0.02


def test_the_configuration_keeps_every_published_width():
    """Every key of the catalog row as published but the three of
    ``reduced``, each with its published value and the deployment
    beside it (``test_bench_files.py`` holds the entry to the same)."""
    published = {
        "attention_bias": False, "first_k_dense_replace": 1,
        "hidden_act": "silu", "hidden_size": 5120,
        "intermediate_size": 12288, "kv_lora_rank": 512,
        "max_position_embeddings": 163840, "model_type": "deepseek_v2",
        "moe_intermediate_size": 1536, "moe_layer_freq": 1, "n_group": 8,
        "n_shared_experts": 2, "norm_topk_prob": False,
        "num_attention_heads": 128, "num_experts_per_tok": 6,
        "num_key_value_heads": 128, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "routed_scaling_factor": 16, "scoring_func": "softmax",
        "seq_aux": True, "tie_word_embeddings": False, "topk_group": 3,
        "topk_method": "group_limited_greedy", "v_head_dim": 128,
        "rope_scaling": {
            "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
            "mscale_all_dim": 0.707,
            "original_max_position_embeddings": 4096, "type": "yarn"}}
    for key, value in published.items():
        assert BIG[key] == value, key
    assert BIG["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert BIG["published"] == {"num_hidden_layers": 60,
                                "n_routed_experts": 160,
                                "vocab_size": 102400}
    assert (BIG["num_hidden_layers"], BIG["n_routed_experts"],
            BIG["vocab_size"]) == (5, 40, 25600)
    assert BIG["deployment"]["chips_sharing_a_layer"] == 4
    assert BIG["experts_held"] == [0, 40]
    # the floors of the model-configs guide
    assert BIG["num_hidden_layers"] - BIG["first_k_dense_replace"] >= 4
    assert BIG["n_routed_experts"] >= 8 and BIG["vocab_size"] * 8 >= 102400
    assert len(BIG["assumed"]) >= 8 and len(BIG["left_out"]) == 1
    cfg = kind.deepseek_config(BIG)
    assert cfg.n_routed_experts == 160 and cfg.experts_held == (0, 40)
    assert cfg.rope_scaling.factor == 40 and cfg.stack == "deepseek"
    assert deepseekref.sizes(BIG)["router_outputs"] == 160


@pytest.mark.parametrize("config", [BIG, TINY], ids=lambda c: c["name"])
def test_check_config_and_the_cut(config):
    kind.check_config(config)
    assert kinds.of(config) is kind
    assert set(config["published"]) == set(config["reduced"])
    for key in config["reduced"]:
        assert config[key] != config["published"][key]
    assert config["deployment"]["chips_sharing_a_layer"] == 4
    broken = dict(config, experts_held=[0, config["n_routed_experts"] + 1])
    with pytest.raises(AssertionError):
        kind.check_config(broken)
    with pytest.raises(AssertionError):
        kind.check_config(dict(config, scoring_func="sigmoid"))


@pytest.mark.parametrize("name", NEW)
def test_new_metric_file_names_what_it_reads(name):
    spec = json.loads((ROOT / "perfbench" / "metrics"
                       / f"{name}.json").read_text())
    args = spec["args"]
    assert args["program"] in spec["what"]
    for counter in ("experts_hit", "attention_pairs"):
        if counter in args:
            assert args[counter]["family"] in spec["what"]


def test_the_kind_takes_from_textgen_what_does_not_know_the_model():
    from perfbench.kinds import textgen

    for name in ("UNIT", "PROGRAM_MODULES", "word", "ids_of", "job",
                 "job_size", "decode_artifact", "served_rows", "check_mix"):
        assert getattr(kind, name) is getattr(textgen, name), name
    for name in ("seeded_params", "build", "reference_logprobs", "check",
                 "control", "job_flops", "decode_bytes", "kernel_sites",
                 "check_config"):
        assert getattr(kind, name) is not getattr(textgen, name), name
    big = traffic.make_job(kind, 0, [16384, 64, 16], 2 ** 31 + 5, BIG, "m")
    words = big["prompt"].split()
    assert len(words) == 16384 and all(len(w) == 4 for w in words)
    assert max(kind.ids_of(big["prompt"], BIG)) < 25600
    assert (big["max_new_tokens"], big["num_return_sequences"]) == (64, 16)


# ---- the two copies of the reference -------------------------------------


@pytest.fixture(scope="module")
def tiny_params():
    return kind.seeded_params(TINY, 2 ** 31 + 7, None)


def test_the_two_copies_of_the_reference_agree(tiny_params):
    """``perfbench/deepseekref.py`` and ``tests/deepseek_reference.py``
    share no code; float32 both, so they differ by rounding (logits ~3:
    1e-4 is ten times what they read apart)."""
    import deepseek_reference

    ids = np.random.RandomState(3).randint(0, 96, 23)
    c = deepseekref.sizes(TINY)
    mine = np.asarray(deepseekref.forward(tiny_params, c, ids))
    sizes = {**{k: TINY[k] for k in deepseek_reference.NAMES
                if k != "experts_held"},
             "experts_held": TINY["experts_held"],
             "rope_scaling": TINY["rope_scaling"]}
    theirs = np.asarray(deepseek_reference.forward(tiny_params, sizes, ids))
    assert mine.shape == (23, 96)
    assert np.abs(mine - theirs).max() < 1e-4
    assert np.abs(mine).max() > 0.5


def test_one_pass_over_shared_rows_is_the_pass_over_each(tiny_params):
    c = deepseekref.sizes(TINY)
    rng = np.random.RandomState(4)
    prompt, rows = rng.randint(0, 96, 13), rng.randint(0, 96, (2, 5))
    tree = np.asarray(deepseekref.forward_tree(tiny_params, c, prompt, rows))
    for r in range(2):
        whole = np.asarray(deepseekref.forward(
            tiny_params, c, np.concatenate([prompt, rows[r]])))
        assert np.abs(tree[r] - whole[12:17]).max() < 1e-4
    logprobs = deepseekref.token_logprobs(tree, rows)
    assert logprobs.shape == (2, 5) and (logprobs < 0).all()


def test_attention_in_head_groups_is_attention_over_all_heads(
        tiny_params, monkeypatch):
    """The reference up-projects ``HEAD_GROUP`` heads at a time so that
    128 heads fit; the groups are independent, so any group size gives
    the same logits (the tiny preset has 4 heads: groups of 1 and 4)."""
    ids = np.random.RandomState(5).randint(0, 96, 17)
    c = deepseekref.sizes(TINY)
    outs = []
    for group in (1, 4):
        monkeypatch.setattr(deepseekref, "HEAD_GROUP", group)
        deepseekref._attn_block.clear_cache()
        outs.append(np.asarray(deepseekref.forward(tiny_params, c, ids)))
    # float32 sums in another order, on logits of a few units
    assert np.abs(outs[0] - outs[1]).max() < 5e-5


def test_the_seeded_weights_are_the_layout_at_the_kinds_scales(tiny_params):
    layer = tiny_params["layers"][2]
    assert layer["mlp"]["experts"]["gate"].shape == (4, 64, 32)
    assert layer["mlp"]["router"].shape == (64, 16)
    assert layer["mlp"]["shared"]["gate"].shape == (64, 64)
    assert tiny_params["embed"].shape == (96, 64)
    for name in ("attn_norm", "mlp_norm"):
        assert np.allclose(np.asarray(layer[name]), 1.0)
    assert np.allclose(np.asarray(layer["attn"]["q_norm"]), 1.0)
    assert np.allclose(np.asarray(layer["attn"]["kv_norm"]), 1.0)

    def std(leaf):
        return float(np.asarray(leaf).std())

    # fan-in scaled but for the two gains: W_uq (fan-in 24) x 1.64 and
    # W_o (fan-in 64) x 3, which let the comparison see attention
    assert kind.GAINS == {"wuq": 1.64, "wo": 3.0}
    assert std(np.asarray(layer["attn"]["wuq"]).reshape(24, 4, 24)[..., :16]
               ) == pytest.approx(1.64 * 24 ** -0.5, rel=0.05)
    assert std(layer["attn"]["wo"]) == pytest.approx(
        3.0 * 64 ** -0.5, rel=0.05)
    assert std(layer["attn"]["wukv"]) == pytest.approx(24 ** -0.5, rel=0.05)
    assert std(layer["mlp"]["router"]) == pytest.approx(64 ** -0.5, rel=0.1)
    # a stacked expert kernel is scaled by its own fan-in (64)
    assert std(layer["mlp"]["experts"]["gate"]) == pytest.approx(
        64 ** -0.5, rel=0.1)
    # the shared component: the embedding's mean, size / fan-in on every
    # column of W_dq and on the rope columns of W_uq (a head's last 8 of
    # 24) and of W_dkv (past the 24 latent ones), the two that read the
    # normed input grown by (1 + layer)^0.5; nowhere else
    assert kind.LEAVES == {"embed": (0.2, 1.0)}
    assert kind.SHARED == {"wdq": 1.0, "wuq": 8.0, "wdkv": 9.0}

    def mean(leaf):
        return float(np.asarray(leaf, np.float64).mean())

    assert mean(tiny_params["embed"]) == pytest.approx(0.2, abs=0.03)
    attn = layer["attn"]
    wuq = np.asarray(attn["wuq"]).reshape(24, 4, 24)
    assert mean(wuq[..., 16:]) == pytest.approx(8.0 / 24, abs=0.04)
    assert abs(mean(wuq[..., :16])) < 0.04
    wdkv = np.asarray(attn["wdkv"])
    assert mean(wdkv[:, 24:]) == pytest.approx(9.0 * 3 ** 0.5 / 64, abs=0.02)
    assert abs(mean(wdkv[:, :24])) < 0.01
    assert mean(attn["wdq"]) == pytest.approx(3 ** 0.5 / 64, abs=0.01)
    first = tiny_params["layers"][0]["attn"]
    assert mean(np.asarray(first["wdkv"])[:, 24:]) == pytest.approx(
        9.0 / 64, abs=0.02)
    for leaf in (attn["wukv"], attn["wo"], layer["mlp"]["router"],
                 tiny_params["head"]):
        assert abs(mean(leaf)) < 0.01
    # what reads the normed residual is blind to its shared mean: every
    # column sums to zero over its fan-in (the terms above apart)
    assert kind.BLIND == ("wdq", "wdkv", "router", "gate", "up", "head")
    for leaf in (wdkv[:, :24], layer["mlp"]["router"], tiny_params["head"],
                 layer["mlp"]["experts"]["gate"], layer["mlp"]["shared"]["up"],
                 tiny_params["layers"][0]["mlp"]["gate"]):
        assert np.abs(np.asarray(leaf, np.float64).sum(-2)).max() < 1e-4
    assert np.abs(np.asarray(layer["mlp"]["experts"]["down"],
                             np.float64).sum(-2)).max() > 0.1
    again = kind.seeded_params(TINY, 2 ** 31 + 7, None)
    other = kind.seeded_params(TINY, 2 ** 31 + 8, None)
    assert np.array_equal(np.asarray(again["head"]),
                          np.asarray(tiny_params["head"]))
    assert not np.array_equal(np.asarray(other["head"]),
                              np.asarray(tiny_params["head"]))


def test_the_seeded_weights_give_the_recent_tokens_weight(monkeypatch):
    """What lets the comparison see a row's suffix cache: with the shared
    component a head's query puts a good part of its softmax on the last
    8 of 4,096 keys (at YaRN's 32 published frequencies), without it the
    8 / 4096 of weights that know no position. Layer 0 at a width
    between the tiny cut's and the cell's, by the program's own
    functions."""
    import jax
    import jax.numpy as jnp

    from chiaswarm_tpu.models import deepseek, text_layers

    config = dict(BIG, hidden_size=512, q_lora_rank=256, kv_lora_rank=64,
                  num_attention_heads=8, v_head_dim=32, intermediate_size=64,
                  moe_intermediate_size=8, vocab_size=1024,
                  num_hidden_layers=1,
                  serving=dict(BIG["serving"], dtype="float32"))
    cfg = kind.deepseek_config(config)
    n, queries, last = 4096, 32, 8
    ids = np.random.RandomState(5).randint(0, cfg.vocab_size, (1, n))

    def share_of_the_last():
        params = kind.seeded_params(config, 2 ** 31 + 5, None)
        p = params["layers"][0]["attn"]
        x = text_layers.rms_norm(params["embed"][ids],
                                 params["layers"][0]["attn_norm"],
                                 cfg.rms_norm_eps)
        told = deepseek._told(cfg)
        q_n, q_r, entry = text_layers._query_and_entry(
            p, cfg, x, deepseek._query(p, cfg, x), jnp.arange(n),
            told["inv_freq"], told["rope_amplitude"])
        w_uk, _ = text_layers._up_projections(p, cfg)
        k_n = jnp.einsum("sc,chd->shd", entry[0, :, :cfg.kv_lora_rank], w_uk)
        scores = told["scale"] * (
            jnp.einsum("thd,shd->ths", q_n[0, -queries:], k_n)
            + jnp.einsum("thd,sd->ths", q_r[0, -queries:],
                         entry[0, :, cfg.kv_lora_rank:]))
        t = jnp.arange(n - queries, n)[:, None, None]
        key = jnp.arange(n)[None, None]
        weights = jax.nn.softmax(jnp.where(key <= t, scores, -1e30), -1)
        return float(jnp.where(key > t - last, weights, 0.0).sum(-1).mean())

    assert share_of_the_last() > 0.1            # 0.36-0.49 over seeds
    monkeypatch.setitem(kind.LEAVES, "embed", (0.0, 1.0))
    monkeypatch.setattr(kind, "SHARED", {})
    assert share_of_the_last() < 0.01           # 0.002: 8 of 4096 keys


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
    phases are read anywhere."""
    result = drive(monkeypatch, trace=True, seed=2 ** 31 + 62)
    assert set(result["metrics"]) == {
        "hive_queue_s.lat", "upload_s.lat", "admission_s.lat",
        "moe_tokens_per_expert.lat", "moe_experts_hit.lat"}
    per_expert = result["metrics"]["moe_tokens_per_expert.lat"]["value"]
    assert 1.0 <= per_expert <= 3.0     # at most the rows of a job
    assert 0.0 < result["metrics"]["moe_experts_hit.lat"]["value"] <= 4.0
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

MLA = 5120 * 1536 + 1536 * 128 * 192 + 5120 * 576 + 512 * 128 * 256 \
    + 128 * 128 * 5120                                  # 149.2 M a layer
EXPERT = 3 * 5120 * 1536                                # 23.6 M
OUTSIDE = 5 * MLA + 3 * 5120 * 12288 + 4 * 2 * EXPERT   # outside the routed
HEAD = 5120 * 25600


def test_job_flops_decode_flops_and_decode_bytes_against_hand_counts():
    job = traffic.make_job(kind, 0, [16384, 64, 16], 1, BIG, "m")
    assert MLA == 149_225_472 and EXPERT == 23_592_960
    # a token: every weight outside the routed experts, the router's 160
    # outputs, and 6 x 40/160 = 1.5 held experts, multiply-adds as two
    token = 2.0 * (OUTSIDE + 4 * 5120 * 160 + 4 * 1.5 * EXPERT)
    decoded = 16 * 63
    pairs = kind.decode_pairs(BIG, job)
    assert pairs == 5 * 16 * (63 * 16385 + 63 * 62 // 2)
    decode = decoded * (token + 2.0 * HEAD) + 2.0 * 128 * (576 + 512) * pairs
    assert kind.decode_flops(BIG, job, pairs) == pytest.approx(decode)
    prefill = 16384 * token \
        + 5 * 2.0 * 128 * 320 * 16384 * 16385 / 2 + 2.0 * HEAD
    assert kind.job_flops(BIG, job) == pytest.approx(prefill + decode)
    # ~97 TFLOP of prefill (55 of them attention), ~26 of decode (23)
    assert 95e12 < prefill < 100e12 and 25e12 < decode < 27.5e12
    assert 2.0 * 128 * 1088 * pairs == pytest.approx(23.0e12, rel=0.02)
    shorter = dict(job, max_new_tokens=32)
    assert kind.job_flops(BIG, shorter) < kind.job_flops(BIG, job)
    # a step: 2.5 GB outside the routed experts (the float32 routers and
    # the head included), 94 MB of the prompt's latents and the suffixes;
    # and 47.2 MB for each expert hit
    step = 2 * (OUTSIDE + HEAD) + 4 * 4 * 5120 * 160 \
        + 5 * (16384 + 16 * 64 / 2) * 576 * 2
    none_hit = kind.decode_bytes(BIG, job, 0.0)
    assert none_hit == pytest.approx(63 * step)
    assert 2.55e9 < step < 2.65e9
    assert kind.decode_bytes(BIG, job, 63 * 4 * 18.0) - none_hit \
        == pytest.approx(63 * 4 * 18 * 2 * EXPERT)
    assert kind.kernel_sites(BIG) == []


def fake_context(registry_before, registry_after, config=BIG, traced=None,
                 unit=(16384, 64, 16)):
    job = traffic.make_job(kind, 0, list(unit), 1, BIG, "m")
    return readers.Context(
        workload={"name": CELL}, config=config, mix={}, latencies=[1.0],
        ran={"before": {"registry": registry_before, "stepper": {}},
             "after": {"registry": registry_after, "stepper": {}},
             "traced": traced, "sent": {"a": {"job": job}}},
        good=[{"id": "a", "record": {}}], window_s=10.0,
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        capture=None)


#: a traced window in the plain form, cut at both ends: the first decode
#: event begins with the line, the last ends with it
CUT_FORM = {"window_s": 5.0, "modules": [
    ["jit_text_decode(1)", 1_000, 400_000_000],          # cut at the start
    ["jit_text_prefill(2)", 500_000_000, 150_000_000],
    ["jit_text_prefill(2)", 660_000_000, 152_000_000],
    ["jit_text_prefill(2)", 820_000_000, 190_000_000],
    ["jit_text_decode(1)", 1_100_000_000, 1_000_000_000],
    ["jit_add(3)", 2_200_000_000, 900],
    ["jit_text_decode(1)", 2_300_000_000, 1_020_000_000],
    ["jit_text_prefill(2)", 3_400_000_000, 100_000_000]]}  # cut at the end


def test_program_whole_leaves_out_the_events_the_trace_cut(monkeypatch):
    from perfbench import programs

    assert program_whole.whole_ms(CUT_FORM, "jit_text_decode") \
        == [1000.0, 1020.0]
    assert program_whole.whole_ms(CUT_FORM, "jit_text_prefill") \
        == [150.0, 152.0, 190.0]
    assert program_whole.whole_ms(CUT_FORM, "jit_stepper_step") == []
    assert program_whole.whole_ms({"modules": []}, "jit_text_decode") == []
    # the mean over every event, which the older reader takes, reads a
    # third lower on the same window
    assert programs.mean_ms(CUT_FORM, "jit_text_decode") \
        == pytest.approx(2420.0 / 3)
    context = fake_context({}, {}, traced={"dir": "x", "window_s": 5.0})
    monkeypatch.setattr(programs, "load", lambda d, w: CUT_FORM)
    assert readers.read("decode_whole_ms.lat", context) == 1010.0
    assert readers.read("prefill_whole_ms.lat", context) == 152.0
    only_cut = {"window_s": 5.0, "modules": CUT_FORM["modules"][:1]
                + CUT_FORM["modules"][-1:]}
    monkeypatch.setattr(programs, "load", lambda d, w: only_cut)
    assert readers.read("decode_whole_ms.lat", context) is None
    assert readers.read("decode_whole_ms.lat",
                        fake_context({}, {})) is None      # no trace


def counted(hit, pairs):
    return {"chiaswarm_moe_experts_hit_total": {"values": {"": hit}},
            "chiaswarm_text_attention_pairs_total": {
                "values": {"decode": pairs, "prefill": 7.0 * pairs}}}


def test_decode_roofline_cannot_pass_100(monkeypatch):
    """On a fixture whose one whole event takes exactly the larger of
    the two bounds the share is 100; at the cell's sizes that bound is
    the bytes' (the weights outside the routed experts and ~18 experts a
    layer a step), three times the operations'."""
    from perfbench import programs

    job = traffic.make_job(kind, 0, [16384, 64, 16], 1, BIG, "m")
    pairs, hit = float(kind.decode_pairs(BIG, job)), 63 * 4 * 18.0
    flops_s = kind.decode_flops(BIG, job, pairs) / 197e12
    bytes_s = kind.decode_bytes(BIG, job, hit) / 819e9
    assert 2.5 * flops_s < bytes_s  # 0.13 s of operations, 0.46 s of bytes
    bound_ns = int(max(flops_s, bytes_s) * 1e9)

    def form(dur_ns):
        return {"window_s": 5.0, "modules": [
            ["jit_text_prefill(2)", 0, 1_000],
            ["jit_text_decode(1)", 10_000, dur_ns],
            ["jit_text_prefill(2)", 10 ** 10, 1_000]]}

    context = fake_context(counted(0.0, 0.0), counted(hit, pairs),
                           traced={"dir": "x", "window_s": 5.0})
    monkeypatch.setattr(programs, "load", lambda d, w: form(bound_ns))
    assert readers.read("decode_roofline.lat", context) \
        == pytest.approx(100.0, rel=1e-6)
    monkeypatch.setattr(programs, "load", lambda d, w: form(2 * bound_ns))
    assert readers.read("decode_roofline.lat", context) \
        == pytest.approx(50.0, rel=1e-6)
    # the share is the larger of two sums: never their sum
    both = 100.0 * (flops_s + bytes_s) / (2 * bound_ns * 1e-9)
    assert readers.read("decode_roofline.lat", context) < both


def prefill_runs(kernel_ns):
    """A traced window of a closed loop in the two plain forms: three
    chunks of a job the capture opened in, two whole jobs (eight chunks
    with the small program that adds up their stats between them, then
    the decode), five chunks of one it closed in; in every chunk five
    calls of the kernel (a layer each) of ``kernel_ns`` beside a fusion
    that is not the kernel's."""
    modules, device, at = [], [], 1_000
    for chunks in (3, 8, 8, 5):
        for _ in range(chunks):
            modules.append(["jit_text_prefill(2)", at, 100_000_000])
            for layer in range(5):
                start = at + layer * 20_000_000
                device.append([f"%causal_flash_attention.{layer} = bf16[1,"
                               "2048,16384]{2,1,0} custom-call(s32[1]{0} "
                               "%p)", start, kernel_ns])
                device.append(["%fusion.7 = bf16[2048,5120]{1,0} fusion("
                               "%p)", start + kernel_ns, 1_000])
            modules.append(["jit_add(3)", at + 100_000_010, 900])
            at += 100_010_000
        modules.append(["jit_text_decode(1)", at, 800_000_000])
        at += 800_000_100
    del modules[-2:]     # the capture closed inside the last job's prefill
    return ({"window_s": 5.0, "modules": modules},
            {"window_s": 5.0, "device": device, "host": []})


def test_prefill_attention_counts_against_hand_counts():
    job = traffic.make_job(kind, 0, [16384, 64, 16], 1, BIG, "m")
    assert kind.prefill_chunks(BIG, job) == 8
    short = traffic.make_job(kind, 0, [2049, 64, 16], 1, BIG, "m")
    assert kind.prefill_chunks(BIG, short) == 2
    pairs = 5 * 16384 * 16385 // 2         # what the program's counter adds
    assert kind.prefill_attention_flops(BIG, job, pairs) \
        == 2.0 * 128 * (128 + 64 + 128) * pairs            # 55.0 TFLOP
    # a layer: 16,384 queries and read-outs of 128 x 320 values, the keys
    # and values (128 x 256 + the 64 shared rope values) of 2048 x (1 +
    # ... + 8) tokens, two bytes each: 30.9 GB a job, 0.04 s against the
    # operations' 0.28 s
    assert kind.prefill_attention_bytes(BIG, job) == 5 * 2 * (
        16384 * 128 * 320 + 2048 * 36 * (128 * 256 + 64))
    assert 7 * kind.prefill_attention_bytes(BIG, job) / 819e9 \
        < kind.prefill_attention_flops(BIG, job, pairs) / 197e12


def test_causal_flash_attention_roofline_reads_whole_prefills_only(
        monkeypatch):
    """Two whole prefills of eight chunks in the window; the chunks of
    the jobs the capture cut are left out. A kernel that takes exactly
    the operations' time reads 100, one twice as slow 50."""
    from perfbench import programs, trace
    from perfbench.readers import prefill_attention_roofline as reader

    name = "causal_flash_attention_roofline.lat"
    job = traffic.make_job(kind, 0, [16384, 64, 16], 1, BIG, "m")
    pairs = 5.0 * 16384 * 16385 / 2
    least_s = kind.prefill_attention_flops(BIG, job, pairs) / 197e12
    call_ns = round(least_s * 1e9 / 40)          # 8 chunks x 5 layers
    after = counted(0.0, pairs / 7.0)            # {prefill} = 7 x {decode}
    for slow, share in ((1, 100.0), (2, 50.0)):
        forms = prefill_runs(slow * call_ns)
        runs = reader.whole_runs(forms[0]["modules"], "jit_text_prefill", 8)
        assert len(runs) == 2
        for cut in (3, 5):      # the runs the capture opened or closed in
            assert reader.whole_runs(forms[0]["modules"],
                                     "jit_text_prefill", cut) == []
        assert reader.kernel_seconds(
            forms[1]["device"], "causal_flash_attention", runs) \
            == pytest.approx(80 * slow * call_ns * 1e-9)
        monkeypatch.setattr(programs, "load", lambda d, w: forms[0])
        monkeypatch.setattr(trace, "load", lambda d, w: forms[1])
        context = fake_context(counted(0.0, 0.0), after,
                               traced={"dir": "x", "window_s": 5.0})
        assert readers.read(name, context) == pytest.approx(share, rel=1e-6)
    # no whole prefill in the window: nothing to read
    cut = {"window_s": 5.0, "modules": forms[0]["modules"][:7]}
    monkeypatch.setattr(programs, "load", lambda d, w: cut)
    context = fake_context(counted(0.0, 0.0), after,
                           traced={"dir": "x", "window_s": 5.0})
    assert readers.read(name, context) is None
    assert readers.read(name, fake_context(counted(0.0, 0.0), after)) is None


def test_the_new_readers_find_nothing_on_a_program_without_the_counter():
    """The parent commit has no ``chiaswarm_text_attention_pairs_total``:
    the reader returns None and does not raise; a kind without
    ``decode_flops`` (the Ling cell's, a diffusion cell's) is silent."""
    hit = {"chiaswarm_moe_experts_hit_total": {"values": {"": 40.0}}}
    parent = fake_context({}, hit, traced={"dir": "/nowhere",
                                           "window_s": 5.0})
    for name in NEW:
        assert readers.read(name, parent) is None, name
    ling = json.loads((ROOT / "perfbench" / "configs"
                       / "ling-3.0-flash-vl.json").read_text())
    other = fake_context(counted(0.0, 0.0), counted(40.0, 1e9), config=ling,
                         traced={"dir": "/nowhere", "window_s": 5.0})
    assert readers.read("decode_roofline.lat", other) is None
    assert readers.read("causal_flash_attention_roofline.lat", other) is None
