"""The seam is enough for a second kind of job: ``img2txt`` on a BLIP
preset with a cut in depth and vocabulary (``tests/bench/kinds/caption.py``,
its configuration and its mix: files of the tests', none under
``perfbench/``, no cell) goes through ``cell.run_cell`` as a diffusion
cell does and gives a result line of the contract's shape, ``correct``
by its own number (``logit_gap``), not correct under its control one
precision down, and not correct when an answer is altered where it is
produced."""

import base64
import http.server
import json
import os
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT))

from perfbench import cell, kinds, traffic  # noqa: E402

CELL = "blip-tiny-cut.caption"
MIX = json.loads((HERE / "traffic" / "caption.json").read_text())
#: the benchmark such a cell would run under: the contract's two
#: end-to-end metrics and the per-layer metrics a text job can report
BENCH = {
    "end_to_end": [
        {"name": "job_p50_s", "unit": "s", "workloads": [CELL]},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": [
        {"name": name, "unit": unit, "workloads": [CELL]}
        for name, unit in (("hive_queue_s.lat", "s"), ("upload_s.lat", "s"),
                           ("admission_s.lat", "s"),
                           ("lane_fill_pct.lat", "%"),
                           ("step_mfu.lat", "%"),
                           ("flash_roofline.lat", "%"),
                           ("device_idle_pct.lat", "%"))]}


def load_config(base_uri="http://127.0.0.1:0"):
    config = json.loads(
        (HERE / "configs" / "blip-tiny-cut.json").read_text())
    config["serving"]["image_base_uri"] = base_uri
    return config


@pytest.fixture(scope="module")
def config():
    """The configuration with the address of a local server of the jobs'
    start images in it (the worker fetches ``start_image_uri`` with a
    HEAD and a GET, as from any host)."""
    plain = load_config()
    caption = kinds.of(plain)

    class Images(http.server.BaseHTTPRequestHandler):
        def _send(self, body: bool) -> None:
            blob = caption.image_png(
                plain, int(self.path.rsplit("/", 1)[1].split(".")[0]))
            self.send_response(200)
            self.send_header("Content-Type", "image/png")
            self.send_header("Content-Length", str(len(blob)))
            self.end_headers()
            if body:
                self.wfile.write(blob)

        def do_HEAD(self):
            self._send(False)

        def do_GET(self):
            self._send(True)

        def log_message(self, *args):
            pass

    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Images)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        yield load_config(f"http://127.0.0.1:{server.server_address[1]}")
    finally:
        server.shutdown()
        server.server_close()
        thread.join(timeout=10)
        assert not thread.is_alive()


def drive(monkeypatch, config, *, trace, seed, seconds=1.0):
    monkeypatch.setenv("SWARM_TPU_ROOT", os.environ["SWARM_TPU_ROOT"])
    workload = {"name": CELL, "config": config["name"],
                "traffic": "caption", "chips": 1}
    return cell.run_cell(
        workload=workload, config=config, mix=MIX, benchmark=BENCH,
        seed=seed, seconds=seconds, trace=trace, t_start=time.monotonic(),
        require_tpu=False, out=sys.stderr)


def test_untraced_run_is_correct_by_the_kinds_own_number(monkeypatch,
                                                         config):
    result = drive(monkeypatch, config, trace=False, seed=2 ** 31 + 41)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
    assert set(result["metrics"]) == {"job_p50_s", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["attempted"] >= 4 and result["failed"] == 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    compared = result["compared"]
    assert set(compared) == {"logit_gap", "programs_compiled_in_window"}
    gap = compared["logit_gap"]
    assert gap["limit"] == config["compare"]["logit_gap_limit"]
    # float32 program against float32 reference: rounding and no more
    assert 0 <= gap["value"] < 0.02 * gap["limit"]
    assert result["correct"] is (
        compared["programs_compiled_in_window"]["value"] == 0)
    json.dumps(result)


def test_traced_run_captures_the_kinds_own_program_modules(monkeypatch,
                                                           config):
    """``PROGRAM_MODULES`` names ``pipelines.caption``, not the diffusion
    pipeline: its programs are the ones captured, and the host metrics a
    text job's flight record holds are the ones reported."""
    from perfbench import hlo

    seen = []
    real = hlo.ProgramCapture.patching

    def patching(self, *modules):
        seen.append((self, [m.__name__ for m in modules]))
        return real(self, *modules)

    monkeypatch.setattr(hlo.ProgramCapture, "patching", patching)
    result = drive(monkeypatch, config, trace=True, seed=2 ** 31 + 42)
    (capture, names), = seen
    assert names == ["chiaswarm_tpu.pipelines.caption"]
    assert capture.executables, "the vision tower's program was captured"
    assert set(result["metrics"]) == {"hive_queue_s.lat", "upload_s.lat",
                                      "admission_s.lat"}
    assert "busy_s" not in result["device"] and "breakdown" not in result
    assert result["compared"]["logit_gap"]["value"] \
        <= result["compared"]["logit_gap"]["limit"]


def test_an_answer_altered_where_it_is_produced_is_not_correct(monkeypatch,
                                                              config):
    """The decode hands back another third token than the one it chose
    (and went on from)."""
    from chiaswarm_tpu.pipelines import caption as caption_mod

    real = caption_mod.generate_text
    vocab, sep = config["vocab_size"], config["sep_token_id"]

    def altered(*args, **kwargs):
        ids = np.array(real(*args, **kwargs))
        if sep not in ids[0, :3].tolist():
            ids[0, 2] = (ids[0, 2] + 7) % (vocab - 1)
        return ids

    monkeypatch.setattr(caption_mod, "generate_text", altered)
    result = drive(monkeypatch, config, trace=False, seed=2 ** 31 + 43)
    gap = result["compared"]["logit_gap"]
    assert result["failed"] == 0 and gap["value"] > 10 * gap["limit"]
    assert result["correct"] is False


def test_the_control_one_precision_down_is_not_correct(config):
    """``run.py --control N`` past the look for a chip: the configuration
    states float32, so at each position the token the bfloat16 reference
    puts first is read against the float32 logits."""
    workload = {"name": CELL, "config": config["name"],
                "traffic": "caption", "chips": 1}
    result = cell.run_control(workload=workload, config=config, mix=MIX,
                              seed=2 ** 31 + 44, n_jobs=4,
                              require_tpu=False)
    assert result["control"] == "bfloat16" and result["attempted"] == 4
    gap = result["compared"]["logit_gap"]
    assert gap["limit"] == config["compare"]["logit_gap_limit"]
    assert gap["value"] > 3 * gap["limit"]
    assert result["correct"] is False
    assert list(result)[-1] == "compared"


# ---- the kind's own pieces ------------------------------------------------


@pytest.fixture(scope="module")
def served():
    """(config, params, job, the tokens the program's own pipeline
    decodes for the job): ``CaptionPipeline`` called directly, as the
    worker's callback calls it."""
    from chiaswarm_tpu.models.tokenizer import WordPieceTokenizer
    from chiaswarm_tpu.pipelines.caption import CaptionPipeline
    import dataclasses

    config = load_config()
    caption = kinds.of(config)
    seed = 2 ** 31 + 45
    components = caption._components(config)
    params = caption._seeded(components, config, seed, None)
    pipe = CaptionPipeline(dataclasses.replace(
        components, params=params,
        tokenizer=WordPieceTokenizer(caption.vocabulary(config))),
        max_new_tokens=config["serving"]["max_new_tokens"])
    job = traffic.make_job(caption, 0, 4, seed, config, "m")
    text = pipe(caption.image_pixels(config, job["seed"]), job["prompt"])
    return config, params, job, text


def test_reference_puts_the_programs_tokens_first(served):
    config, params, job, text = served
    caption = kinds.of(config)
    assert text.startswith(job["prompt"] + " ")
    def uploaded(caption_text):
        blob = json.dumps({"caption": caption_text}).encode()
        return {"artifacts": {"primary": {
            "blob": base64.b64encode(blob).decode()}}}

    tokens = caption.served_tokens(uploaded(text), config, job)
    assert len(tokens) == config["serving"]["max_new_tokens"]
    logits = caption.reference_logits(params, config, job, tokens)
    assert logits.shape == (len(tokens), config["vocab_size"])
    assert caption._gap(logits, tokens) < 1e-4
    # another token at one position lies far below the best there
    wrong = list(tokens)
    wrong[3] = (wrong[3] + 7) % (config["vocab_size"] - 1)
    assert caption._gap(logits, wrong) > 1e-2
    # a caption that is not of this vocabulary, or not of this prompt,
    # has no tokens to compare
    for bad in (text + " zebra", text + " tok7", text.split(" ", 1)[1]):
        assert caption.served_tokens(uploaded(bad), config, job) is None


def test_the_cut_is_what_the_file_says(served):
    config, params, _job, _text = served
    decoder = params["decoder"]["params"]
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert [k for k in decoder if k.startswith("layer_")] == ["layer_0"]
    assert decoder["word_embeddings"]["embedding"].shape == (512, 32)
    assert decoder["decoder"]["kernel"].shape == (32, 512)
    assert config["published"] == {"num_hidden_layers": 2,
                                   "vocab_size": 1000}


def test_every_seed_gets_the_same_units_and_its_own_jobs():
    config = load_config("http://h")
    caption = kinds.of(config)
    for seed in (0, 7, 2 ** 31 + 12345):
        counts = traffic.units(MIX, caption.UNIT, 20, seed)
        assert counts == traffic.units(MIX, caption.UNIT, 20, seed)
        for i in (0, 10):
            assert sorted(counts[i:i + 10]) == [0] * 5 + [4] * 5
    a = traffic.make_job(caption, 3, 4, 5, config, "m")
    assert a == traffic.make_job(caption, 3, 4, 5, config, "m")
    assert a != traffic.make_job(caption, 3, 4, 6, config, "m")
    assert len(a["prompt"].split()) == 4 and a["workflow"] == "img2txt"
    assert a["start_image_uri"] == f"http://h/{a['seed']}.png"
    assert traffic.make_job(caption, 3, 0, 5, config, "m")["prompt"] == ""
    solo, burst = traffic.warm_jobs(caption, MIX, 5, config, "m")
    assert [unit for unit, _job in solo] == [0, 4] and burst == []
    assert [traffic.unit_label(unit) for unit, _ in solo] == ["0", "4"]


def test_a_longer_job_counts_more_operations_and_no_kernel_site():
    config = load_config()
    caption = kinds.of(config)
    short, long = ({"prompt": " ".join(["aab"] * n)} for n in (0, 4))
    assert 0 < caption.job_flops(config, short) \
        < caption.job_flops(config, long)
    assert caption.kernel_sites(config) == []
