"""The ``textgen_solar`` kind (``perfbench/kinds/textgen_solar.py``) at
the small size on the CPU: its configuration files and the cut's
arithmetic, the cell and its metrics in the benchmark's lists (membership
only: never an exact list or a last position, which the next cell would
turn red), the plain reference, a run through ``cell.run_cell``, the
control, the work of a job against hand counts, and the reader and two
metric files that came with it."""

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

from perfbench import cell, kinds, readers, solarref, traffic  # noqa: E402
from perfbench.kinds import textgen  # noqa: E402
from perfbench.kinds import textgen_solar as kind  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "solar-open2-250b.sample16"
LING_CELL = "ling-3.0-flash-vl.sample32"
BIG = json.loads((ROOT / "perfbench" / "configs"
                  / "solar-open2-250b.json").read_text())
TINY = json.loads((HERE / "configs" / "solar-tiny-cut.json").read_text())
MIX = json.loads((HERE / "traffic" / "sample-tiny.json").read_text())
NEW = ["recurrence_prefill_ms.lat", "recurrence_decode_roofline.lat"]
LISTED = ["hive_queue_s.lat", "upload_s.lat", "admission_s.lat",
          "step_mfu.lat", "device_idle_pct.lat", "text_prefill_s.lat",
          "text_decode_s.lat", "moe_tokens_per_expert.lat",
          "moe_experts_hit.lat", "prefill_whole_ms.lat",
          "decode_whole_ms.lat", "decode_roofline.lat",
          "causal_flash_attention_roofline.lat",
          "shared_prompt_attention_roofline.lat", "decode_experts_ms.lat",
          "decode_attention_ms.lat", "prefill_experts_ms.lat",
          "decode_experts_roofline.lat"]
TINY_CELL = "solar-tiny-cut.sample-tiny"
TINY_BENCH = {
    "end_to_end": [
        {"name": "job_p50_s", "unit": "s", "workloads": [TINY_CELL]},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": [
        {"name": m["name"], "unit": m["unit"], "workloads": [TINY_CELL]}
        for m in BENCH["per_layer"] if CELL in m["workloads"]]}


# ---- the files -----------------------------------------------------------


def test_the_cell_and_its_metrics_are_in_the_lists():
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "sample16"
    assert entry["config"] == "solar-open2-250b"
    config = next(c for c in BENCH["configs"]
                  if c["name"] == "solar-open2-250b")
    assert config["file"] == "perfbench/configs/solar-open2-250b.json"
    assert config["source"] == BIG["source"] == (
        "https://huggingface.co/upstage/Solar-Open2-250B/blob/main/"
        "config.json")
    mix = traffic.load_mix("sample16")
    assert mix[kind.UNIT] == [[[16384, 64, 16], 1.0]]
    kind.check_mix(mix)
    p50 = next(m for m in BENCH["end_to_end"] if m["name"] == "job_p50_s")
    assert CELL in p50["workloads"]
    mine = {m["name"] for m in BENCH["per_layer"] if CELL in m["workloads"]}
    assert set(LISTED + NEW) <= mine
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW:
        m = by_name[name]
        assert {CELL, LING_CELL} <= set(m["workloads"])
        assert m["moves"] == "job_p50_s" and m["source"] == "device_trace"
        assert m["layer"] == "text programs"
    assert by_name[NEW[1]]["unit"] == "%"
    assert by_name[NEW[1]]["better"] == "higher"
    assert by_name[NEW[0]]["better"] == "lower"


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog row as published but the three of
    ``reduced``, each with its published value and the 8-chip deployment
    beside it."""
    published = {
        "model_type": "solar_open2", "partial_rotary_factor": 1,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                               "num_heads": 64, "num_kv_heads": None},
        "hidden_size": 4096, "num_attention_heads": 64, "head_dim": 128,
        "num_key_value_heads": 8, "intermediate_size": 10240,
        "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05,
        "rope_theta": 10000, "tie_word_embeddings": False,
        "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
        "use_rope": False, "gqa_interval": 3,
        "gqa_layers": list(range(0, 48, 4)), "use_gqa_gate": True,
        "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "num_experts_per_tok": 8}
    for key, value in published.items():
        assert BIG[key] == value, key
    assert BIG["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert BIG["published"] == {"num_hidden_layers": 48,
                                "n_routed_experts": 320,
                                "vocab_size": 196608}
    assert (BIG["num_hidden_layers"], BIG["n_routed_experts"],
            BIG["vocab_size"]) == (6, 40, 24576)
    assert BIG["experts_held"] == [0, 40]
    assert BIG["deployment"]["chips_sharing_a_layer"] == 8
    # the floors: a whole period (G K K K) and four layers past it at
    # least, 8 experts or more, an eighth of the vocabulary
    assert BIG["num_hidden_layers"] >= 4
    assert BIG["n_routed_experts"] >= 8
    assert BIG["vocab_size"] * 8 == BIG["published"]["vocab_size"]
    assert BIG["n_routed_experts"] * 8 == BIG["published"]["n_routed_experts"]
    assumed = " ".join(BIG["assumed"])
    for reading in ("rank 128", "elementwise", "sigmoid scores",
                    "1280", "num_kv_heads null"):
        assert reading in assumed, reading
    assert any("intermediate_size 10240" in x for x in BIG["left_out"])
    cfg = kind.solar_config(BIG)
    assert cfg.stack == "solar" and cfg.n_routed_experts == 320
    assert cfg.experts_held == (0, 40) and cfg.vocab_size == 24576
    assert cfg.softmax_layers == [0, 4] and cfg.kda_layers == [1, 2, 3, 5]
    assert solarref.sizes(BIG)["router_outputs"] == 320


def test_the_cut_is_the_issues_arithmetic():
    """One expert 3 x 4096 x 1280 = 15.73 M (31.5 MB); a delta-rule layer
    ~154.8 M and a softmax layer ~126.1 M outside the routed experts; four
    and two of them with 40 experts each, plus the embedding's and head's
    slices: 4.85 B parameters, 9.71 GB (the routers float32), under the
    registry's 75% of the chip with room for the caches."""
    import jax

    from chiaswarm_tpu.models import solar

    w = kind._weights(BIG)
    assert w["expert"] == 3 * 4096 * 1280 == 15_728_640
    outside_kda = w["kda"] + w["shared"] + w["router"]
    outside_gqa = w["softmax"] + w["shared"] + w["router"]
    assert 154.0e6 < outside_kda < 155.5e6
    assert 125.5e6 < outside_gqa < 126.5e6
    shapes = solar.param_shapes(kind.solar_config(BIG))
    count = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    resident = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                   for s in jax.tree.leaves(shapes))
    assert 4.84e9 < count < 4.86e9
    assert 9.70e9 < resident < 9.72e9
    assert resident < 0.75 * 16.9e9 - 1.5e9
    # a seventh layer (a delta-rule one) would add 783.9 M
    assert 11.2e9 < resident + 2 * (outside_kda + 40 * w["expert"]) < 11.4e9


@pytest.mark.parametrize("config", [BIG, TINY], ids=lambda c: c["name"])
def test_check_config_and_the_cut(config):
    kind.check_config(config)
    assert kinds.of(config) is kind
    assert set(config["published"]) == set(config["reduced"])
    for key in config["reduced"]:
        assert config[key] != config["published"][key]
    for wrong in ({"use_rope": True}, {"kda_allow_neg_eigval": False},
                  {"kda_use_full_proj": True},
                  {"experts_held": [0, 1 + config["n_routed_experts"]]}):
        with pytest.raises(AssertionError):
            kind.check_config(dict(config, **wrong))


@pytest.mark.parametrize("name", NEW)
def test_new_metric_file_names_what_it_reads(name):
    spec = json.loads((ROOT / "perfbench" / "metrics"
                       / f"{name}.json").read_text())
    args = spec["args"]
    assert args["scope"] == "recurrence" and args["scope"] in spec["what"]
    assert args["program"] in spec["what"]
    if name == NEW[1]:
        assert spec["reader"] == "recurrence_roofline"
        assert args["state_bytes"]["family"] in spec["what"]
        assert args["state_bytes"]["labels"] == "decode"
    else:
        assert spec["reader"] == "scope_device" and args["run"] is True


def test_the_kind_takes_from_textgen_what_does_not_know_the_model():
    for name in ("UNIT", "PROGRAM_MODULES", "word", "ids_of", "job",
                 "job_size", "decode_artifact", "served_rows", "check_mix"):
        assert getattr(kind, name) is getattr(textgen, name), name
    # check and control: textgen's own code, reading this kind's reference
    for name in ("check", "control"):
        assert getattr(kind, name).__code__ is getattr(textgen,
                                                       name).__code__
        assert getattr(kind, name).__globals__ is vars(kind)
    for name in ("seeded_params", "build", "reference_logprobs",
                 "job_flops", "decode_bytes", "check_config"):
        assert getattr(kind, name) is not getattr(textgen, name), name
    big = traffic.make_job(kind, 0, [16384, 64, 16], 2 ** 31 + 5, BIG, "m")
    ids = kind.ids_of(big["prompt"], BIG)
    assert len(ids) == 16384 and max(ids) < 24576
    assert kind.kernel_sites(BIG) == []


# ---- the reference -------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_params():
    return kind.seeded_params(TINY, 2 ** 31 + 7, None)


def test_one_pass_over_shared_rows_is_the_pass_over_each(tiny_params):
    """A row's recurrence starts from the state and conv history after
    the prompt, its attention sees the prompt's keys and its own."""
    c = solarref.sizes(TINY)
    rng = np.random.RandomState(4)
    prompt, rows = rng.randint(0, 96, 13), rng.randint(0, 96, (2, 11))
    tree = np.asarray(solarref.forward_tree(tiny_params, c, prompt, rows))
    for r in range(2):
        whole = np.asarray(solarref.forward(
            tiny_params, c, np.concatenate([prompt, rows[r]])))
        assert np.abs(tree[r] - whole[12:23]).max() < 1e-4
    logprobs = solarref.token_logprobs(tree, rows)
    assert logprobs.shape == (2, 11) and (logprobs < 0).all()


def test_the_seeded_weights_are_the_layout_at_the_kinds_scales(tiny_params):
    import jax

    layer = tiny_params["layers"][1]
    attn = layer["attn"]
    assert attn["wa_down"].shape == (64, 8) and attn["wa_up"].shape == (8, 16)
    assert layer["mlp"]["experts"]["gate"].shape == (4, 64, 32)
    assert layer["mlp"]["router"].shape == (64, 16)
    dt = np.asarray(attn["dt_bias"], np.float64)
    assert -9.0 <= dt.min() and dt.max() <= -1.0
    rate = np.exp(np.asarray(attn["a_log"], np.float64))
    assert 1.0 <= rate.min() and rate.max() <= 16.0
    assert float(np.asarray(attn["wb"]).std()) == pytest.approx(
        kind.GAINS["wb"] * 64 ** -0.5, rel=0.15)
    # b = 2 sigmoid(W_b x) passes 1 for about half the pairs, 1.5 for ~29%
    x = np.random.RandomState(1).randn(4000, 64)
    b = 2.0 / (1.0 + np.exp(-x @ np.asarray(attn["wb"], np.float64)))
    assert 0.4 < (b > 1.0).mean() < 0.6
    assert 0.22 < (b > 1.5).mean() < 0.36
    # the softmax layers' self match: W_q of head j holds SELF x W_k of
    # its key-value head j // 4
    gqa = tiny_params["layers"][0]["attn"]
    wq = np.asarray(gqa["wq"], np.float64).reshape(64, 2, 4, 16)
    wk = np.asarray(gqa["wk"], np.float64).reshape(64, 2, 1, 16)
    noise = wq - kind.SELF * wk
    assert np.abs(noise.std() - 64 ** -0.5) < 0.2 * 64 ** -0.5
    assert abs(np.corrcoef(wq[:, 1, 3].ravel(), wk[:, 1, 0].ravel())[0, 1]
               - 2 ** -0.5) < 0.1
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
    result = drive(monkeypatch, trace=False, seed=2 ** 31 + 71)
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
    result = drive(monkeypatch, trace=True, seed=2 ** 31 + 72)
    assert set(result["metrics"]) == {
        "hive_queue_s.lat", "upload_s.lat", "admission_s.lat",
        "moe_tokens_per_expert.lat", "moe_experts_hit.lat"}


def test_the_control_one_precision_down_is_not_correct():
    workload = {"name": TINY_CELL, "config": TINY["name"],
                "traffic": "sample-tiny", "chips": 1}
    result = cell.run_control(workload=workload, config=TINY, mix=MIX,
                              seed=2 ** 31 + 74, n_jobs=3,
                              require_tpu=False)
    assert result["control"] == "bfloat16" and result["attempted"] == 3
    assert result["compared"]["logprob_gap_median"]["value"] \
        > 3 * result["compared"]["logprob_gap_median"]["limit"]
    assert result["correct"] is False


# ---- the work of a job, and the readers ----------------------------------


def big_job():
    return traffic.make_job(kind, 0, [16384, 64, 16], 1, BIG, "m")


KDA = 4 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64
GQA = 3 * 4096 * 8192 + 2 * 4096 * 1024
EXPERT = 3 * 4096 * 1280
HEAD = 4096 * 24576


def test_job_flops_decode_flops_and_decode_bytes_against_hand_counts():
    job = big_job()
    # a token: every weight outside the routed experts, the router's 320
    # outputs, 8 chosen x 40/320 held, the recurrence's 8 D^2 a head
    token = 2.0 * (4 * KDA + 2 * GQA + 6 * (4096 * 320 + EXPERT
                                            + EXPERT * 8 * 40 / 320)) \
        + 4 * 8.0 * 64 * 128 ** 2
    assert kind._token_flops(BIG) == pytest.approx(token)
    prefill_pairs = 2 * 16384 * 16385 // 2
    assert kind.prefill_pairs(BIG, job) == prefill_pairs
    decode_pairs = 2 * 16 * (63 * 16385 + 63 * 62 // 2)
    assert kind.decode_pairs(BIG, job) == decode_pairs
    decode = 16 * 63 * (token + 2.0 * HEAD) \
        + 4.0 * 128 * 64 * decode_pairs
    assert kind.decode_flops(BIG, job, decode_pairs) == pytest.approx(decode)
    prefill = 16384 * token + 4.0 * 128 * 64 * prefill_pairs + 2.0 * HEAD
    assert kind.job_flops(BIG, job) == pytest.approx(prefill + decode)
    # the issue's reckoning: the two softmax layers' 8.8 TFLOP
    assert 4.0 * 128 * 64 * prefill_pairs == pytest.approx(8.8e12, rel=0.01)
    # a step: the weights outside the routed experts once, the states
    # read and written (16 rows x 4 layers x 4 MiB x 2 = 0.54 GB and the
    # conv tails), the softmax layers' keys and values; 31.5 MB an expert
    state = 4 * 16 * (64 * 128 * 128 * 4 + 3 * 3 * 8192 * 2)
    assert kind.recurrent_state_bytes(BIG, 16) == state
    assert 0.54e9 < 2 * state / 1.0 < 0.56e9
    assert 1.09e9 < kind.recurrence_weight_bytes(BIG) < 1.11e9
    none_hit = kind.decode_bytes(BIG, job, 0.0)
    assert kind.decode_bytes(BIG, job, 63 * 6 * 13.0) - none_hit \
        == pytest.approx(63 * 6 * 13 * 2 * EXPERT)
    # the issue's ~5.1 GB a step with ~13 experts hit a layer
    assert kind.decode_bytes(BIG, job, 63 * 6 * 13.0) / 63 \
        == pytest.approx(5.1e9, rel=0.03)


def test_the_kinds_counts_are_the_programs():
    """The pairs and the state bytes the kind prices are what
    ``models/solar.py::job_counts`` feeds the counters."""
    from chiaswarm_tpu.models import solar

    job = big_job()
    counts = solar.job_counts(kind.solar_config(BIG), 16384, 16, 64, 2048,
                              16384)
    assert counts["attention_pairs"] == (kind.prefill_pairs(BIG, job),
                                         kind.decode_pairs(BIG, job))
    assert counts["recurrent_state_bytes"]["decode"] \
        == 63 * 2 * kind.recurrent_state_bytes(BIG, 16)
    assert kind.prefill_chunks(BIG, job) == 8
    # the decode sweep reads the prompt's keys and values once a layer a
    # step: 63 x 2 x 33.6 MB
    assert kind.decode_attention_bytes(BIG, job) > 63 * 2 * 2 * 8 * 128 * 2 \
        * 16384


def fake_context(before, after, config=BIG, cell_name=CELL, job=None):
    return readers.Context(
        workload={"name": cell_name}, config=config, mix={},
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


def decode_trace(scoped_ns, copy_ns, other_ns, state="f32[16,64,128,128]"):
    """A traced window's join for the reader (``scope_device._joined``'s
    form): two whole decodes between a prefill and a cut one, in each a
    fusion under the scope recurrence, XLA's unscoped copy and slice of
    a state, and an unscoped fusion of another shape."""
    ops = {"fusion.1": {("f32", (16, 64, 128)): frozenset({"recurrence"})},
           "copy-done.3": {("f32", (16, 64, 128, 128)): frozenset()},
           "slice-done.4": {("f32", (4, 64, 128, 128)): frozenset()},
           "fusion.9": {("bf16", (8, 4096)): frozenset()}}
    modules, device, at = [["jit_text_prefill(2)", 0, 1_000]], [], 10_000
    length = scoped_ns + 2 * copy_ns + other_ns + 100
    for _ in range(2):
        modules.append(["jit_text_decode(1)", at, length])
        device += [
            [f"%fusion.1 = f32[16,64,128]{{2,1,0}} fusion(%a)", at + 10,
             scoped_ns],
            [f"%copy-done.3 = {state}{{3,2,1,0}} copy-done(%b)",
             at + 20 + scoped_ns, copy_ns],
            [f"%slice-done.4 = {state.replace('16,', '4,')}{{3,2,1,0}} "
             "slice-done(%c)", at + 30 + scoped_ns + copy_ns, copy_ns],
            ["%fusion.9 = bf16[8,4096]{1,0} fusion(%d)",
             at + 40 + scoped_ns + 2 * copy_ns, other_ns]]
        at += 2 * length
    modules.append(["jit_text_decode(1)", at, length])
    return device, {"jit_text_decode": ops}, modules


def test_the_recurrence_roofline_is_its_bytes_over_its_time(monkeypatch):
    """Time is the scope's plus XLA's unscoped copies and slices of the
    float32 states: a recurrence that takes exactly its bytes' time reads
    100, one twice as slow 50, whatever the other operations take; the
    Ling cell's bytes come from its configuration (the Ling kind has no
    function for them); a program whose delta-rule layers do not count
    their state (the parent's), or a kind without delta-rule layers,
    reads None and does not raise."""
    from perfbench.readers import recurrence_roofline, scope_device

    state = 63 * 2 * kind.recurrent_state_bytes(BIG, 16)
    moved = state + 63 * kind.recurrence_weight_bytes(BIG)
    least_ns = moved / 819e9 * 1e9
    for slow, share in ((1, 100.0), (2, 50.0)):
        # a fifth of the time in the two unscoped state operations
        joined = decode_trace(round(slow * least_ns * 0.6),
                              round(slow * least_ns * 0.2), 5_000_000)
        monkeypatch.setattr(scope_device, "_joined", lambda c: joined)
        context = fake_context(counted(0.0), counted(float(state)))
        assert readers.read(NEW[1], context) \
            == pytest.approx(share, rel=1e-5)
    # a state of another layout is not the delta rule's
    other = decode_trace(round(least_ns * 0.6), round(least_ns * 0.2), 0,
                         state="f32[16,32,128,128]")
    monkeypatch.setattr(scope_device, "_joined", lambda c: other)
    assert readers.read(NEW[1], context) == pytest.approx(100.0 / 0.6,
                                                          rel=1e-5)
    assert readers.read(NEW[1], fake_context({}, {})) is None
    ling = json.loads((ROOT / "perfbench" / "configs"
                       / "ling-3.0-flash-vl.json").read_text())
    ling_job = traffic.make_job(textgen, 0, [16384, 128, 32], 1, ling, "m")
    context = fake_context(counted(0.0), counted(1e9), config=ling,
                           cell_name=LING_CELL, job=ling_job)
    # seven KDA layers of six 2560 x 4096 projections and W_b
    assert recurrence_roofline.weight_bytes(context) \
        == 7 * 2 * (6 * 2560 * 4096 + 2560 * 32)
    assert recurrence_roofline.state_dims(ling) == (32, 128, 128)
    assert recurrence_roofline.state_dims(BIG) == (64, 128, 128)
    laguna = json.loads((ROOT / "perfbench" / "configs"
                         / "laguna-xs.2.json").read_text())
    other = fake_context(counted(0.0), counted(1e9), config=laguna)
    assert recurrence_roofline.weight_bytes(other) is None
    assert readers.read(NEW[1], other) is None
    monkeypatch.setattr(scope_device, "_joined", lambda c: None)
    assert readers.read(NEW[1], fake_context(counted(0.0), counted(1e9))) \
        is None


def test_the_new_metrics_find_nothing_off_the_chip():
    parent = readers.Context(
        workload={"name": CELL}, config=BIG, mix={}, latencies=[1.0],
        ran={"before": {"registry": {}, "stepper": {}},
             "after": {"registry": {}, "stepper": {}},
             "traced": {"dir": "/nowhere", "window_s": 5.0},
             "sent": {"a": {"job": big_job()}}},
        good=[{"id": "a", "record": {}}], window_s=10.0,
        device={"platform": "cpu", "kind": "cpu", "count": 1}, capture=None)
    for name in NEW:
        assert readers.read(name, parent) is None, name
