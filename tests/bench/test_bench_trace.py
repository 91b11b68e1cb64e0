"""The reduction from a trace to numbers, on hand-made events with
known answers and on a small trace recorded on the chip
(perfbench/fixtures/trace_small.json, cut from a traced sd15-512.backlog
window)."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import flops, hlo, trace  # noqa: E402

MS = 1_000_000

#: 10 ms window. Device: a convolution fusion twice (2 ms each), a flash
#: call overlapping the first by 1 ms, a while that spans everything.
HAND = {
    "window_s": 0.010,
    "device": [
        ["%while.1 = (s32[]) while(...)", 0, 9 * MS],
        ["%fusion.7 = bf16[2,64,64,320] fusion(...)", 1 * MS, 2 * MS],
        ["%flash.3 = bf16[16,4096,40] custom-call(...)", 2 * MS, 2 * MS],
        ["%fusion.7 = bf16[2,64,64,320] fusion(...)", 6 * MS, 2 * MS],
    ],
    "host": [
        ["swarm.lane.step", 0, 9 * MS],
        ["inner.wait", 4 * MS + 100, 1 * MS],
    ],
}
COSTS = {
    ("fusion.7", ("bf16", (2, 64, 64, 320))): {
        "flops": 2.0e8, "bytes": 1.0e3, "kind": "conv", "computation": ""},
    # the same name in another program (twice the batch): never joined
    # to this trace's events, whose text says [2,64,64,320]
    ("fusion.7", ("bf16", (4, 64, 64, 320))): {
        "flops": 4.0e8, "bytes": 2.0e3, "kind": "conv", "computation": ""},
    ("flash.3", ("bf16", (16, 4096, 40))): {
        "flops": 1.0e3, "bytes": 1.0e6, "kind": "flash", "computation": ""},
}


def test_busy_is_the_union_and_idle_its_complement():
    assert trace.busy_intervals(HAND) == [(1 * MS, 4 * MS), (6 * MS, 8 * MS)]
    assert trace.busy_seconds(HAND) == pytest.approx(0.005)
    assert trace.idle_share(HAND) == pytest.approx(0.5)
    assert trace.idle_share({"window_s": 1.0, "device": [], "host": []}) \
        is None


def test_per_kernel_time_leaves_containers_out():
    totals = trace.op_totals(HAND)
    assert set(totals) == {"fusion.7", "flash.3"}
    assert totals["fusion.7"]["count"] == 2
    assert totals["fusion.7"]["seconds"] == pytest.approx(0.004)
    assert totals["flash.3"]["seconds"] == pytest.approx(0.002)


def test_roofline_share_and_its_binding_side():
    # peak 1e11 op/s, 1e9 B/s: the fusion is compute bound (2 ms of
    # bound per call over 2 ms run = 100%), the flash call memory bound
    # (1 ms of bound over 2 ms = 50%)
    conv = trace.kernel_roofline(HAND, COSTS, ("conv", "mixed"), 1e11, 1e9)
    assert conv["share"] == pytest.approx(1.0) and conv["bound"] == "flops"
    flash = trace.kernel_roofline(HAND, COSTS, ("flash",), 1e11, 1e9)
    assert flash["share"] == pytest.approx(0.5) and flash["bound"] == "hbm"
    assert trace.kernel_roofline(HAND, COSTS, ("dot",), 1e11, 1e9) is None


def test_a_cost_of_none_gives_no_share():
    costs = dict(COSTS)
    costs[("flash.3", ("bf16", (16, 4096, 40)))] = dict(
        costs[("flash.3", ("bf16", (16, 4096, 40)))], flops=None, bytes=None)
    assert trace.kernel_roofline(HAND, costs, ("flash",), 1e11, 1e9) is None


FLASH_HLO = """HloModule step

ENTRY %main (q: bf16[16,4096,128], k: bf16[16,{s},128]) -> bf16[16,4096,128] {{
  %q = bf16[16,4096,128]{{2,1,0}} parameter(0)
  %k = bf16[16,{s},128]{{2,1,0}} parameter(1)
  ROOT %flash_attention.3 = bf16[16,4096,128]{{2,1,0}} custom-call(%q, %k, %k), custom_call_target="tpu_custom_call", metadata={{op_name="flash_attention"}}
}}
"""


@pytest.mark.parametrize("kv, want", [(4096, (4096, 4096, 40)),
                                      (128, (4096, 77, 40))])
def test_a_flash_call_is_costed_at_the_stated_attention(kv, want):
    """SD1.5 at 512 px: head size 40 in a 128-lane operand. The padded
    count (the original's) is 3.2 times the stated one."""
    config = json.loads(
        (ROOT / "perfbench" / "configs" / "sd15-512.json").read_text())
    sites = flops.attention_sites(config, 512, 512)
    assert {(4096, 4096, 40), (1024, 1024, 80), (256, 256, 160),
            (64, 64, 160), (4096, 77, 40)} <= set(sites)
    text = FLASH_HLO.format(s=kv)
    cost = hlo.parse_hlo_text(text, sites)["flash_attention.3"]
    l, s, d = want
    assert cost["kind"] == "flash"
    assert cost["flops"] == 4.0 * 16 * l * s * d
    assert cost["bytes"] == 2 * 16 * d * (2 * l + 2 * s)
    padded = hlo.parse_hlo_text(text)["flash_attention.3"]
    assert padded["flops"] == 4.0 * 16 * 4096 * kv * 128
    assert padded["flops"] / cost["flops"] >= 3.2
    # a call that holds none of the stated attentions has no cost
    none = hlo.parse_hlo_text(text, [(8192, 8192, 40)])["flash_attention.3"]
    assert none["flops"] is None and none["bytes"] is None


def test_sdxl_states_head_size_64_at_both_flash_levels():
    config = json.loads(
        (ROOT / "perfbench" / "configs" / "sdxl-1024.json").read_text())
    sites = flops.attention_sites(config, 1024, 1024)
    assert hlo.match_site(4096, 4096, 128, sites) == (4096, 4096, 64)
    assert hlo.match_site(1024, 1024, 128, sites) == (1024, 1024, 64)
    assert hlo.match_site(16384, 16384, 512, sites) == (16384, 16384, 512)
    assert hlo.match_site(32, 32, 128, sites) is None


def test_idle_gaps_are_named_by_the_innermost_host_span():
    assert trace.idle_gaps(HAND) == [["inner.wait", pytest.approx(0.002)]]
    bare = dict(HAND, host=[])
    assert trace.idle_gaps(bare) == [["unattributed", pytest.approx(0.002)]]


@pytest.fixture(scope="module")
def recorded():
    return json.loads(
        (ROOT / "perfbench" / "fixtures" / "trace_small.json").read_text())


def test_recorded_trace_reduces_to_its_pinned_numbers(recorded):
    form, want = recorded["form"], recorded["expected"]
    # busy by an independent sweep over sorted endpoints
    points = []
    for name, start, dur in form["device"]:
        if not trace.is_container(trace.op_name(name)) and dur > 0:
            points += [(start, 1), (start + dur, -1)]
    depth = busy = last = 0
    for t, step in sorted(points):
        if depth > 0:
            busy += t - last
        depth, last = depth + step, t
    assert trace.busy_seconds(form) == pytest.approx(busy * 1e-9, rel=1e-9)
    assert trace.busy_seconds(form) == pytest.approx(want["busy_s"])
    assert trace.idle_share(form) == pytest.approx(want["idle_share"])
    totals = trace.op_totals(form)
    assert len(totals) == want["distinct_ops"]
    top = max(totals.items(), key=lambda kv: kv[1]["seconds"])
    assert top[0] == want["top_op"]
    assert top[1]["seconds"] == pytest.approx(want["top_op_s"])
    assert 0 < trace.busy_seconds(form) <= form["window_s"]
    gaps = trace.idle_gaps(form)
    assert gaps and all(seconds > 0 for _name, seconds in gaps)
