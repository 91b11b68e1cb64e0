"""Every file the benchmark finds by name parses and holds to the
contract's characters; every entry of BENCHMARK.json has its files. What
a configuration's and a mix's file must hold beyond that is its kind's
to say (``perfbench/kinds/<kind>.py::check_config`` / ``check_mix``)."""

import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import kinds  # noqa: E402

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


#: a configuration of a second kind, owned by the tests (the entry it
#: would have in BENCHMARK.json): held to the same rules as the cells'
CAPTION = {
    "name": "blip-tiny-cut",
    "source": "chiaswarm_tpu/models/blip.py::BLIP_TINY (tests only; no cell "
              "of BENCHMARK.json uses it)",
    "file": "tests/bench/configs/blip-tiny-cut.json",
    "reduced": ["num_hidden_layers", "vocab_size"],
    "why": "tests only: a text-out job (img2txt) with a cut in depth and "
           "vocabulary, to hold the harness's seam to a second kind"}


@pytest.mark.parametrize("entry", BENCH["configs"] + [CAPTION],
                         ids=lambda e: e["name"])
def test_configuration_file(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"]) and one_line(entry["why"])
    assert one_line(entry["source"])
    assert any(entry["file"].startswith(f"{top}/") for top in BENCH["paths"])
    config = json.loads((ROOT / entry["file"]).read_text())
    assert config["name"] == entry["name"]
    assert config["source"] == entry["source"]
    # a cut is listed on both sides, each key with the published value
    # beside it, under the deployment the cut stands for
    assert config["reduced"] == entry["reduced"]
    assert len(entry["reduced"]) <= 16
    assert all(NAME.match(key) for key in entry["reduced"])
    assert set(config.get("published", {})) == set(entry["reduced"])
    for key in entry["reduced"]:
        assert key in config and config[key] != config["published"][key]
    if entry["reduced"]:
        deployment = config["deployment"]
        chips = deployment["chips_sharing_a_layer"]
        assert isinstance(chips, int) and chips >= 1
        assert one_line(deployment["how"])
    for group in ("kind", "serving", "hive", "worker_settings", "compare",
                  "assumed"):
        assert group in config, group
    # every departure from a deployed worker's settings says why
    assert set(config["worker_settings"]) - {"hive_token", "worker_name"} \
        <= set(config["worker_settings_why"])
    # the first job and one that followed a settlement are both compared
    assert config["compare"]["jobs"] >= 3
    kinds.of(config).check_config(config)


def check_mix(mix, kind):
    """A mix under the kind of the configuration it is run with."""
    assert mix["loop"] == "closed" and mix["clients"] >= 1
    assert abs(sum(share for _, share in mix[kind.UNIT]) - 1.0) < 1e-9
    kind.check_mix(mix)


def test_the_second_kinds_mix_holds_to_the_same_rules():
    config = json.loads((ROOT / CAPTION["file"]).read_text())
    mix = json.loads((ROOT / "tests" / "bench" / "traffic"
                      / "caption.json").read_text())
    check_mix(mix, kinds.of(config))


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(cell[k]) for k in ("name", "config", "traffic"))
    assert one_line(cell["why"]) and cell["chips"] in (1, 4)
    assert cell["config"] in {c["name"] for c in BENCH["configs"]}
    mix = json.loads(
        (ROOT / "perfbench" / "traffic" / f"{cell['traffic']}.json"
         ).read_text())
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    kind = kinds.of(json.loads((ROOT / entry["file"]).read_text()))
    check_mix(mix, kind)
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
        assert any("mfu" in n for n in mine), cell
        assert any("idle" in n for n in mine), cell
    used = {c["config"] for c in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


METRIC_FILES = sorted((ROOT / "perfbench" / "metrics").glob("*.json"))
DATA_FILES = sorted(p for top in ("perfbench", "tests/bench")
                    for d in ("configs", "traffic")
                    for p in (ROOT / top / d).glob("*.json"))


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
    data = json.loads(path.read_text())
    assert isinstance(data, dict)
    if path.parent.name == "configs":
        kinds.of(data)  # every configuration names a kind that is there
