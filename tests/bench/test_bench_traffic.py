"""The generator is a pure function of (seed, mix, seconds): the same
seed gives the same jobs, and every seed gets the same amount of work."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import traffic  # noqa: E402
from perfbench.kinds import diffusion  # noqa: E402

CONFIG = json.loads(
    (ROOT / "perfbench" / "configs" / "sd15-512.json").read_text())
SEEDS = (0, 7, 2 ** 31 + 12345)


MIXED = {"loop": "closed", "clients": 1,
         "steps": [[20, 0.3], [30, 0.5], [50, 0.2]],
         "warm_solo": [[20, 1], [30, 1], [50, 1]],
         "warm_burst": [[20, 2], [50, 1]]}


def test_step_counts_hold_their_shares_in_every_block():
    mix = MIXED
    for seed in SEEDS:
        counts = traffic.units(mix, "steps", 40, seed)
        assert counts == traffic.units(mix, "steps", 40, seed)
        for i in range(0, 40, 10):
            block = counts[i:i + 10]
            assert (block.count(20), block.count(30), block.count(50)) \
                == (3, 5, 2)
    assert traffic.units(traffic.load_mix("single"), diffusion.UNIT, 5, 3) \
        == [30] * 5


def test_jobs_depend_on_seed_and_index_alone():
    def make(index):
        return traffic.make_job(diffusion, index, 30, 2 ** 31 + 5, CONFIG,
                                "bench/sd15-512")

    a = make(3)
    assert a == make(3) and a != make(4)
    assert a["prompt"].replace(" ", "").isalpha() and a["prompt"].islower()
    assert (a["height"], a["width"], a["guidance_scale"]) == (512, 512, 7.5)
    assert 0 <= a["seed"] < 2 ** 31


#: what PR 27's generator (commit 1e0182e, before a kind made the job)
#: sent for (seed 2**31 + 5, index 7) and as the warm-up's solo job
PINNED = {
    "w00007": ("autumn harbor autumn dusk stone canyon ivory sky",
               1213040002),
    "ws00000": ("velvet paper golden tide dusk silver field distant",
                408006237)}


@pytest.mark.parametrize("name, size", [("sdxl-1024", 1024),
                                        ("sd15-512", 512)])
def test_a_cells_jobs_are_the_ones_it_sent_before_the_kinds(name, size):
    cfg = json.loads(
        (ROOT / "perfbench" / "configs" / f"{name}.json").read_text())
    mix = traffic.load_mix("single")
    seed = 2 ** 31 + 5
    sent = [traffic.make_job(diffusion, 7, 30, seed, cfg, f"bench/{name}"),
            traffic.warm_jobs(diffusion, mix, seed, cfg,
                              f"bench/{name}")[0][0][1]]
    for job in sent:
        prompt, noise = PINNED[job["id"]]
        assert job == {
            "id": job["id"], "model_name": f"bench/{name}",
            "workflow": "txt2img", "prompt": prompt, "seed": noise,
            "num_inference_steps": 30, "guidance_scale": 7.5,
            "height": size, "width": size, "content_type": "image/png"}
        assert list(job) == ["id", "model_name", "workflow", "prompt",
                             "seed", "num_inference_steps",
                             "guidance_scale", "height", "width",
                             "content_type"]


def test_a_unit_names_its_warm_up_split():
    assert traffic.unit_label(30) == "30"
    assert traffic.unit_label([512, 64]) == "512_64"


@pytest.mark.parametrize("mix, config, solo, burst", [
    (traffic.load_mix("single"), "sdxl-1024", [30], 0),
    (traffic.load_mix("single"), "sd15-512", [30], 0),
    (MIXED, "sd15-512", [20, 30, 50], 3)],
    ids=["single-sdxl", "single-sd15", "mixed"])
def test_warm_up_covers_every_step_count_of_the_mix(mix, config, solo, burst):
    cfg = json.loads(
        (ROOT / "perfbench" / "configs" / f"{config}.json").read_text())
    solo_jobs, burst_jobs = (
        [job for _unit, job in jobs]
        for jobs in traffic.warm_jobs(diffusion, mix, 1, cfg, "m"))
    assert [j["num_inference_steps"] for j in solo_jobs] == solo
    assert len(burst_jobs) == burst
    # every step count of the window is warmed solo
    diffusion.check_mix(mix)
    assert len({j["id"] for j in solo_jobs + burst_jobs}) \
        == len(solo_jobs) + len(burst_jobs)


def test_an_open_loop_is_refused_until_a_cell_proves_it(tmp_path, monkeypatch):
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "later.json").write_text(
        json.dumps(dict(MIXED, loop="open")))
    monkeypatch.setattr(traffic, "HERE", tmp_path)
    with pytest.raises(ValueError):
        traffic.load_mix("later")
