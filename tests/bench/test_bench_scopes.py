"""The join of a device trace to the scopes a program writes into its
HLO (``perfbench/scopes.py``) and its two readers (``scope_device``,
``experts_roofline``), on hand-made forms and a few lines of HLO with
known answers."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import scopes  # noqa: E402
from perfbench.readers import (  # noqa: E402
    experts_roofline,
    prefill_attention_roofline,
    program_whole,
    scope_device,
)

MS = 1_000_000
TEXT_CELLS = ["ling-3.0-flash-vl.sample32", "deepseek-v2.sample16",
              "laguna-xs.2.sample32", "solar-open2-250b.sample16",
              "lfm2-8b-a1b.sample32"]
DIFFUSION_CELLS = ["sdxl-1024.single", "sd15-512.single"]

DECODE = """HloModule jit_text_decode, is_scheduled=true

%fused_computation (p: bf16[8,64]) -> bf16[8,64] {
  %p = bf16[8,64]{1,0} parameter(0)
  ROOT %multiply.3 = bf16[8,64]{1,0} multiply(%p, %p), metadata={op_name="jit(text_decode)/experts/mul"}
}

%body (arg: (s32[], bf16[8,64])) -> (s32[], bf16[8,64]) {
  %arg = (s32[], bf16[8,64]{1,0}) parameter(0)
  %x = bf16[8,64]{1,0} get-tuple-element(%arg), index=1
  ROOT %fusion.1 = bf16[8,64]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(text_decode)/while/body/closed_call/experts/while/body/closed_call/experts/dot_general"}
}

ENTRY %main (a: bf16[8,64]) -> f32[8,100] {
  %a = bf16[8,64]{1,0} parameter(0)
  %fusion.2 = f32[8,100]{1,0} fusion(%a), kind=kOutput, calls=%fused_computation, metadata={op_name="jit(text_decode)/head/dot_general;jit(text_decode)/head/convert_element_type"}
  %copy.4 = bf16[8,64]{1,0} copy(%a), metadata={op_name="jit(text_decode)/while/body/closed_call/attention/copy"}
  %add.5 = bf16[8,64]{1,0} add(%a, %a)
  ROOT %while.6 = (s32[], bf16[8,64]{1,0}) while(%a), condition=%cond, body=%body, metadata={op_name="jit(text_decode)/while"}
}
"""

#: the other program: an operation of the same name under another scope
PREFILL = """HloModule jit_text_prefill, is_scheduled=true

ENTRY %main (a: bf16[8,64]) -> bf16[8,64] {
  %a = bf16[8,64]{1,0} parameter(0)
  ROOT %fusion.1 = bf16[8,64]{1,0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(text_prefill)/attention/dot_general"}
}
"""

#: the parent's decode: the same operations, no scope in any op_name
PARENT = DECODE.replace("/experts/", "/moe/").replace("/head/", "/logits/") \
    .replace("/attention/", "/mla/")

#: decode executions at 0 (cut by the window's start), 20 and 40 ms, the
#: prefill between them and last
MODULES = [["jit_text_decode(1)", 0, 10 * MS],
           ["jit_text_prefill(2)", 12 * MS, 5 * MS],
           ["jit_text_decode(1)", 20 * MS, 10 * MS],
           ["jit_text_prefill(2)", 32 * MS, 5 * MS],
           ["jit_text_decode(1)", 40 * MS, 10 * MS],
           ["jit_text_prefill(2)", 52 * MS, 5 * MS]]

DEVICE = [
    ["%fusion.1 = bf16[8,64]{1,0} fusion(%x)", 1 * MS, 9 * MS],    # cut
    ["%fusion.1 = bf16[8,64]{1,0} fusion(%a)", 13 * MS, 2 * MS],   # prefill
    ["%while.6 = (s32[], bf16[8,64]) while(%a)", 20 * MS, 10 * MS],
    ["%fusion.1 = bf16[8,64]{1,0} fusion(%x)", 20 * MS, 2 * MS],
    ["%fusion.1 = bf16[8,64]{1,0} fusion(%x)", 22 * MS, 1 * MS],
    ["%fusion.2 = f32[8,100]{1,0} fusion(%a)", 23 * MS, 3 * MS],
    ["%copy.4 = bf16[8,64]{1,0} copy(%a)", 26 * MS, 1 * MS],
    ["%add.5 = bf16[8,64]{1,0} add(%a, %a)", 27 * MS, 1 * MS],
    ["%fusion.1 = bf16[8,64]{1,0} fusion(%x)", 40 * MS, 4 * MS],
    ["%fusion.2 = f32[8,100]{1,0} fusion(%a)", 44 * MS, 2 * MS],
    ["%fusion.1 = bf16[8,64]{1,0} fusion(%a)", 53 * MS, 3 * MS],   # prefill
]


def context(texts=(DECODE, PREFILL), device=DEVICE, modules=MODULES,
            **more):
    """A traced run's context as the readers see it, with the window's
    join handed in (``scope_device`` keeps it on the context)."""
    ctx = SimpleNamespace(**more)
    ctx._scope_join = (device, scopes.program_maps(texts), modules)
    return ctx


def test_a_path_carries_each_scope_once():
    assert scopes.scopes_of(
        "jit(f)/experts/while/body/closed_call/experts/dot;"
        "jit(f)/experts/x") == {"experts"}
    # a kernel whose name holds a scope's is not under it
    assert scopes.scopes_of("jit(f)/jit(shared_latent_attention)/exp") \
        == frozenset()
    assert scopes.scopes_of("") == frozenset()


def test_two_programs_sharing_an_operation_name_keep_their_own_scopes():
    maps = scopes.program_maps([DECODE, PREFILL])
    assert scopes.event_scopes(maps["jit_text_decode"],
                               DEVICE[3][0]) == {"experts"}
    assert scopes.event_scopes(maps["jit_text_prefill"],
                               DEVICE[1][0]) == {"attention"}
    # an operation inside a fused computation is no event of its own,
    # but its name is in the map; an unknown name has no scopes
    assert scopes.event_scopes(maps["jit_text_decode"],
                               "%fusion.99 = f32[1] fusion()") is None
    # two executables of one program at two shapes: told apart by shape
    wide = DECODE.replace("bf16[8,64]", "bf16[16,64]").replace(
        "/experts/", "/shared_expert/")
    merged = scopes.program_maps([DECODE, wide])["jit_text_decode"]
    assert scopes.event_scopes(merged, "%fusion.1 = bf16[16,64]{1,0} "
                                       "fusion(%x)") == {"shared_expert"}
    assert scopes.event_scopes(merged, DEVICE[3][0]) == {"experts"}


def test_an_execution_the_window_cut_is_left_out():
    assert program_whole.whole_executions(MODULES, "jit_text_decode") \
        == [(20 * MS, 30 * MS), (40 * MS, 50 * MS)]
    # the executions whose time program_whole reads
    form = json.loads((ROOT / "perfbench" / "fixtures"
                       / "trace_programs.json").read_text())
    whole = program_whole.whole_executions(form["modules"],
                                           "jit_stepper_step")
    assert [(b - a) * 1e-6 for a, b in whole] \
        == program_whole.whole_ms(form, "jit_stepper_step")


def test_a_scopes_time_per_execution_and_the_coverage():
    maps = scopes.program_maps([DECODE, PREFILL])
    found = scopes.splits(DEVICE, maps, MODULES, "jit_text_decode")
    assert found[0] == pytest.approx({
        "all": 8.0, "scoped": 7.0, "experts": 3.0, "head": 3.0,
        "attention": 1.0})
    assert found[1] == pytest.approx({
        "all": 6.0, "scoped": 6.0, "experts": 4.0, "head": 2.0})
    prefill = scopes.splits(DEVICE, maps, MODULES, "jit_text_prefill")
    assert prefill == [
        pytest.approx({"all": 2.0, "scoped": 2.0, "attention": 2.0}),
        pytest.approx({"all": 0.0, "scoped": 0.0})]


def test_the_reader_takes_the_median_execution_or_the_mean_run():
    ctx = context()
    assert scope_device.read(ctx, "jit_text_decode", "experts") \
        == pytest.approx(3.5)
    assert scope_device.read(ctx, "jit_text_decode", "head") \
        == pytest.approx(2.5)
    assert scope_device.read(ctx, "jit_text_decode", "attention") \
        == pytest.approx(0.5)
    # a job's two chunks back to back, three runs: at 10 and 30 ms, and
    # at 50 ms one that the window's end cuts
    modules = [["jit_text_decode(1)", 0, 5 * MS],
               ["jit_text_prefill(2)", 10 * MS, 4 * MS],
               ["jit_text_prefill(2)", 14 * MS, 4 * MS],
               ["jit_text_decode(1)", 19 * MS, 9 * MS],
               ["jit_text_prefill(2)", 30 * MS, 4 * MS],
               ["jit_text_prefill(2)", 34 * MS, 4 * MS],
               ["jit_text_decode(1)", 39 * MS, 9 * MS],
               ["jit_text_prefill(2)", 50 * MS, 4 * MS],
               ["jit_text_prefill(2)", 54 * MS, 4 * MS]]
    device = [["%fusion.1 = bf16[8,64]{1,0} fusion(%a)", start * MS,
               dur * MS] for start, dur in ((11, 1), (15, 2), (31, 3),
                                            (34, 4), (51, 9))]
    maps = scopes.program_maps([DECODE, PREFILL])
    assert prefill_attention_roofline.whole_runs(modules,
                                                 "jit_text_prefill", 2) \
        == [(10 * MS, 18 * MS), (30 * MS, 38 * MS)]
    runs = scopes.splits(device, maps, modules, "jit_text_prefill", 2)
    assert [r["attention"] for r in runs] == pytest.approx([3.0, 7.0])
    job = {"prompt": " ".join(["w"] * 3000)}
    ctx = context(device=device, modules=modules,
                  config={"serving": {"prefill_chunk": 2048}},
                  good=[{"id": "a"}, {"id": "b"}],
                  ran={"sent": {"a": {"job": job}, "b": {"job": job}}})
    assert scope_device.read(ctx, "jit_text_prefill", "attention",
                             run=True) == pytest.approx(5.0)


def test_the_split_reports_each_program_and_its_coverage():
    out = scope_device.split(context(), run_program=None)
    decode = out["jit_text_decode"]
    assert decode["experts"] == pytest.approx(3.5)
    assert decode["n"] == 2
    assert decode["coverage"] == pytest.approx(13.0 / 14.0)


def test_nothing_to_read_gives_none():
    # a program built before the scopes: no operation carries one
    parent = context(texts=(PARENT, PREFILL))
    assert scope_device.read(parent, "jit_text_decode", "experts") is None
    # off the chip, or without a trace
    cpu = SimpleNamespace(ran={"traced": {"dir": "/nowhere",
                                          "window_s": 1.0}},
                          device={"platform": "cpu"}, capture=None)
    assert scope_device.read(cpu, "jit_text_decode", "experts") is None
    untraced = SimpleNamespace(ran={}, device={"platform": "tpu"},
                               capture=None)
    assert scope_device.read(untraced, "jit_stepper_step", "resnet") is None
    # no whole execution of the program
    alone = context(modules=MODULES[:1])
    assert scope_device.read(alone, "jit_text_decode", "experts") is None


def registry(hit: float) -> dict:
    return {"registry": {"chiaswarm_moe_experts_hit_total": {
        "values": {"": hit}}}}


def test_the_experts_roofline_is_their_bytes_over_the_scopes_time():
    """1,000 hits over two jobs, each one expert of 3 x 64 x 32 bfloat16
    values (12,288 bytes): 6.144 MB a job over the scope's 3.5 ms."""
    ctx = context(config={"hidden_size": 64, "moe_intermediate_size": 32},
                  good=[{"id": "a"}, {"id": "b"}],
                  ran={"before": registry(100.0),
                       "after": registry(1100.0)},
                  peaks={"hbm_gbps": 819.0})
    share = experts_roofline.read(
        ctx, "jit_text_decode", "experts",
        {"family": "chiaswarm_moe_experts_hit_total", "labels": ""})
    assert share == pytest.approx(
        100.0 * 500 * 12288 / (3.5e-3 * 819e9))
    # a program without the counter reads nothing
    ctx.ran = {"before": {"registry": {}}, "after": {"registry": {}}}
    assert experts_roofline.read(
        ctx, "jit_text_decode", "experts",
        {"family": "chiaswarm_moe_experts_hit_total", "labels": ""}) is None


@pytest.mark.parametrize("name", [
    "decode_experts_ms.lat", "decode_attention_ms.lat",
    "prefill_experts_ms.lat", "decode_experts_roofline.lat",
    "step_resnet_ms.lat", "step_transformer_ms.lat"])
def test_each_new_metric_names_its_scope_and_program(name):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["source"] == "device_trace"
    assert entry["moves"] == "job_p50_s"
    spec = json.loads((ROOT / "perfbench" / "metrics"
                       / f"{name}.json").read_text())
    assert spec["args"]["scope"] in scopes.SCOPES
    assert spec["args"]["scope"] in spec["what"]
    assert spec["args"]["program"] in spec["what"]
    # every cell whose program opens the scope lists the metric
    text = entry["layer"] == "text programs"
    for cell in (TEXT_CELLS if text else DIFFUSION_CELLS):
        assert cell in entry["workloads"], (name, cell)
    assert entry["layer"] == ("text programs" if name.startswith(
        ("decode_", "prefill_")) else "model step")
    assert spec["reader"] == ("experts_roofline" if "roofline" in name
                              else "scope_device")
