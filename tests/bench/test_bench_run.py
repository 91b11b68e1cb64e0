"""The harness end to end on the CPU at tiny sizes: the command refuses
to run without a chip; the rest of a run (everything past the look for a
chip) gives a result line of the contract's shape, names no device metric
off the chip, and comes out not correct when the timed path is broken
underneath."""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import cell, compare, readers, traffic  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def drive(monkeypatch, *, trace, seed, mix="single",
          stands_for="sdxl-1024.single", seconds=1.0):
    """One run of the tiny configuration under a real cell's name (so
    that cell's metrics are the ones reported)."""
    monkeypatch.setenv("SWARM_TPU_ROOT", os.environ["SWARM_TPU_ROOT"])
    config = json.loads(
        (ROOT / "perfbench" / "configs" / "tiny-64.json").read_text())
    workload = {"name": stands_for, "config": "tiny-64", "traffic": mix,
                "chips": 1}
    return cell.run_cell(
        workload=workload, config=config, mix=traffic.load_mix(mix),
        benchmark=BENCH, seed=seed, seconds=seconds, trace=trace,
        t_start=time.monotonic(), require_tpu=False, out=sys.stderr)


def test_command_refuses_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace",
         "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert done.returncode == 2, done.stderr[-2000:]
    assert done.stdout.strip() == ""
    assert "no TPU" in done.stderr


def test_unknown_device_kind_has_no_peaks():
    assert readers.peaks_for("TPU v5 lite")["bf16_tflops"] == 197.0
    with pytest.raises(readers.NoPeaks):
        readers.peaks_for("cpu")
    with pytest.raises(readers.NoPeaks):
        readers.peaks_for("TPU v99")


def test_untraced_run_reports_the_cells_end_to_end_metrics(monkeypatch):
    result = drive(monkeypatch, trace=False, seed=2 ** 31 + 21)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
    assert set(result["metrics"]) == {"job_p50_s", "setup_s"}
    assert result["metrics"]["job_p50_s"]["unit"] == "s"
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["device"]["platform"] == "cpu"
    compared = result["compared"]
    assert set(compared) == {"image_gap", "programs_compiled_in_window"}
    assert compared["image_gap"]["value"] <= compared["image_gap"]["limit"]
    # float32 program against float32 reference: rounding and no more
    assert result["correct"] is (
        compared["programs_compiled_in_window"]["value"] == 0)
    json.dumps(result)


def test_traced_cpu_run_names_no_device_metric(monkeypatch):
    result = drive(monkeypatch, trace=True, seed=2 ** 31 + 22)
    named = set(result["metrics"])
    assert named, "host-side per-layer metrics are still read"
    assert named <= {"hive_queue_s.lat", "upload_s.lat", "admission_s.lat",
                     "lane_fill_pct.lat"}
    assert not {n for n in named if "mfu" in n or "roofline" in n
                or "idle" in n}
    assert "busy_s" not in result["device"] and "breakdown" not in result


def test_an_answer_altered_where_it_is_produced_is_not_correct(monkeypatch):
    """The fault a serving cell can have: the lane's decode hands back
    other pixels than the latents hold (every value moved by 24)."""
    from chiaswarm_tpu.pipelines.diffusion import DiffusionPipeline

    real = DiffusionPipeline.stepper_decode_fn

    def broken(self, **static):
        decode = real(self, **static)
        return lambda params, x: (decode(params, x) // 2 + 24).astype("uint8")

    monkeypatch.setattr(DiffusionPipeline, "stepper_decode_fn", broken)
    result = drive(monkeypatch, trace=False, seed=2 ** 31 + 23)
    gap = result["compared"]["image_gap"]
    assert gap["value"] > gap["limit"]
    assert result["correct"] is False


@pytest.mark.parametrize("name", ["tiny-64", "tinyxl-64"])
def test_the_control_run_comes_out_not_correct(name):
    """``run.py --control N`` past the look for a chip: the reference one
    precision below the configuration's (float32 here, so bfloat16) in
    the program's place, judged by the run's own ``check``."""
    config = json.loads(
        (ROOT / "perfbench" / "configs" / f"{name}.json").read_text())
    workload = {"name": "sdxl-1024.single", "config": name,
                "traffic": "single", "chips": 1}
    result = cell.run_control(
        workload=workload, config=config, mix=traffic.load_mix("single"),
        seed=2 ** 31 + 31, n_jobs=2, require_tpu=False)
    assert result["control"] == "bfloat16" and result["attempted"] == 2
    gap = result["compared"]["image_gap"]
    assert gap["limit"] == config["compare"]["image_gap_limit"]
    assert gap["value"] > 1.2 * gap["limit"]
    assert result["correct"] is False
    assert list(result)[-1] == "compared"


def test_the_sample_holds_the_first_job_and_one_that_followed_it():
    from perfbench.kinds.diffusion import job_size as size

    good = [{"id": f"w{i:05d}", "t": float(i)} for i in range(7)]
    sent = {g["id"]: {"job": {"num_inference_steps": 30}} for g in good}
    for seed in (1, 2, 2 ** 31 + 7):
        ids = [g["id"] for g in compare.pick(good, sent, seed, 3, size)]
        assert ids[:2] == ["w00006", "w00000"] and len(set(ids)) == 3
        assert ids == [g["id"]
                       for g in compare.pick(good, sent, seed, 3, size)]
    assert {compare.pick(good, sent, seed, 3, size)[2]["id"]
            for seed in range(20)} > {"w00003"}
    sent["w00002"]["job"]["num_inference_steps"] = 50  # the longest
    assert [g["id"] for g in compare.pick(good, sent, 1, 2, size)] \
        == ["w00002", "w00000"]
    assert [g["id"] for g in compare.pick(good[:1], sent, 1, 3, size)] \
        == ["w00000"]
    assert compare.pick([], sent, 1, 3, size) == []
