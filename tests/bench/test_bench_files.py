"""Every file the benchmark finds by name parses and holds to the
contract's characters; every entry of BENCHMARK.json has its files."""

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
FILE = re.compile(r"^[A-Za-z0-9_.\-/]+$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51
    assert BENCH["command"][:2] == ["python3", "perfbench/run.py"]
    assert BENCH["paths"] == ["perfbench", "tests/bench"]
    # a full check with all 24 cells fits the driver's 43200 s
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_every_file_under_paths_is_named_from_the_allowed_characters():
    for top in BENCH["paths"]:
        for path in (ROOT / top).rglob("*"):
            if "__pycache__" in path.parts or path.suffix == ".pyc":
                continue
            assert FILE.match(str(path.relative_to(ROOT))), path


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_configuration_file(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"]) and one_line(entry["why"])
    assert one_line(entry["source"]) and entry["file"].startswith("perfbench/")
    config = json.loads((ROOT / entry["file"]).read_text())
    assert config["name"] == entry["name"]
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"] == []
    for group in ("unet", "text_encoders", "vae", "scheduler", "serving",
                  "worker_settings", "compare", "assumed"):
        assert group in config, group
    # every departure from a deployed worker's settings says why
    assert set(config["worker_settings"]) - {"hive_token", "worker_name"} \
        <= set(config["worker_settings_why"])
    assert 0 < config["compare"]["image_gap_limit"] < 1
    # the first job and one that followed a settlement are both compared
    assert config["compare"]["jobs"] >= 3
    # nothing read off the program's internals sits in the file
    assert set(config["serving"]) == {"height", "width", "guidance_scale",
                                      "dtype", "content_type", "workflow"}


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(cell[k]) for k in ("name", "config", "traffic"))
    assert one_line(cell["why"]) and cell["chips"] in (1, 4)
    assert cell["config"] in {c["name"] for c in BENCH["configs"]}
    mix = json.loads(
        (ROOT / "perfbench" / "traffic" / f"{cell['traffic']}.json"
         ).read_text())
    assert mix["loop"] == "closed" and mix["clients"] >= 1
    assert abs(sum(share for _, share in mix["steps"]) - 1.0) < 1e-9
    reported = [m for m in BENCH["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])]
    assert "setup_s" in {m["name"] for m in reported} and len(reported) >= 2
    assert any(cell["name"] in m["workloads"] for m in BENCH["per_layer"])


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells = {c["name"] for c in BENCH["workloads"]}
    assert set(metric.get("workloads", [])) <= cells
    if "bound" in metric:  # end to end
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
        return
    assert set(metric) == {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert one_line(metric["layer"])
    moved = next(m for m in BENCH["end_to_end"]
                 if m["name"] == metric["moves"])
    # every cell that reports the metric reports what it moves
    assert set(metric["workloads"]) <= set(moved.get("workloads", cells))
    spec = json.loads((ROOT / "perfbench" / "metrics"
                       / f"{metric['name']}.json").read_text())
    assert (ROOT / "perfbench" / "readers" / f"{spec['reader']}.py").exists()
    if "roofline" in metric["name"] or "mfu" in metric["name"]:
        assert metric["unit"] == "%" and metric["better"] == "higher"


def test_names_are_unique_and_each_cell_has_mfu_and_idle():
    for group in (METRICS, BENCH["workloads"], BENCH["configs"]):
        names = [entry["name"] for entry in group]
        assert len(names) == len(set(names))
    for cell in BENCH["workloads"]:
        mine = [m["name"] for m in BENCH["per_layer"]
                if cell["name"] in m["workloads"]]
        assert any(n.startswith("step_mfu.") for n in mine), cell
        assert any(n.startswith("device_idle_pct.") for n in mine), cell
    used = {c["config"] for c in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


METRIC_FILES = sorted((ROOT / "perfbench" / "metrics").glob("*.json"))
DATA_FILES = sorted(p for d in ("configs", "traffic")
                    for p in (ROOT / "perfbench" / d).glob("*.json"))


@pytest.mark.parametrize("path", METRIC_FILES, ids=lambda p: p.stem)
def test_metric_file_names_a_reader_that_is_there(path):
    spec = json.loads(path.read_text())
    assert NAME.match(path.stem) and set(spec) <= {"reader", "args", "what"}
    reader = ROOT / "perfbench" / "readers" / f"{spec['reader']}.py"
    assert reader.exists() and "def read(context" in reader.read_text()


def test_no_metric_file_is_parked():
    assert {p.stem for p in METRIC_FILES} \
        == {m["name"] for m in BENCH["per_layer"]}


@pytest.mark.parametrize("path", DATA_FILES,
                         ids=lambda p: f"{p.parent.name}-{p.stem}")
def test_data_file_parses_and_is_named_by_the_rules(path):
    assert NAME.match(path.stem)
    assert isinstance(json.loads(path.read_text()), dict)
