"""chip_smoke.py, the compile-cache placement rule and the worker's
refusal to serve on a backend nobody named — the parts of the chip
bring-up (ISSUE 21) a CPU host can check."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _scrubbed_env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS",
                        "JAX_COMPILATION_CACHE_DIR")}
    env["PYTHONPATH"] = str(REPO)
    env.update(extra)
    return env


def _import_chip_smoke():
    """The script lives at the checkout root, which is not a package."""
    sys.path.insert(0, str(REPO))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(REPO))
    return chip_smoke


def test_smoke_function_passes_on_cpu_at_tiny_size(tmp_path, monkeypatch):
    """The same function ``python chip_smoke.py`` runs at SDXL/1024 on the
    chip, at ``tiny``/64 px here: real Worker + MiniHive, two waves on
    one lane, every assert but the TPU-only ones (Mosaic custom calls,
    device memory stats)."""
    chip_smoke = _import_chip_smoke()
    # run_smoke re-points the settings root; monkeypatch restores it
    monkeypatch.setenv("SWARM_TPU_ROOT", str(tmp_path / "root"))
    # the smoke fills the residency ledger to its budget: keep the fill
    # small on a host whose "HBM" is the 16 GiB stand-in
    monkeypatch.setenv("CHIASWARM_RESIDENCY_BUDGET", str(32 << 20))
    result = chip_smoke.run_smoke(
        "tiny", 64, require_tpu=False, steps=(6, 6, 4),
        attn_shapes=((1, 256, 2, 32),), out_dir=tmp_path / "out")
    assert result["ok"] is True
    assert result["device"]["platform"] == "cpu"
    assert result["sanity"]["jobs_ok"] == 6
    assert len({job["lane"] for job in result["sanity"]["jobs"]}) == 1
    ledger = result["sanity"]["residency"]
    assert ledger["budget_bytes"] == 32 << 20
    assert 0.99 * (32 << 20) < ledger["resident_bytes"] <= 32 << 20
    assert (tmp_path / "out" / "result.json").exists()


def test_last_stdout_line_is_the_verdict_alone(monkeypatch, capsys):
    """What reads the smoke parses its LAST stdout line and accepts only
    ``{"ok", "device": {"platform", "kind", "count"}}`` — the set-up
    facts and sanity values go on the line before it."""
    import json

    chip_smoke = _import_chip_smoke()
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(
        chip_smoke, "run_smoke",
        lambda *a, **kw: {"ok": True, "device": dict(device),
                          "setup": {"model": "sdxl"},
                          "sanity": {"jobs_ok": 6}})
    assert chip_smoke.main([]) == 0
    lines = capsys.readouterr().out.splitlines()
    assert [json.loads(line) for line in lines] == [
        {"setup": {"model": "sdxl"}, "sanity": {"jobs_ok": 6}},
        {"ok": True, "device": device}]


def test_smoke_script_fails_fast_without_a_tpu():
    """``JAX_PLATFORMS=cpu python chip_smoke.py``: non-zero within
    seconds, the missing TPU named on stderr, no result on stdout."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        env=_scrubbed_env(JAX_PLATFORMS="cpu"), cwd=str(REPO),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr and "'cpu'" in proc.stderr
    assert proc.stdout.strip() == ""


# ---- compile cache: placed from outside, never by code when env is set --


def _record_config_updates(monkeypatch):
    import jax

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_cache_dir_env_set_means_code_sets_no_directory(monkeypatch,
                                                        tmp_path):
    from chiaswarm_tpu.core.compile_cache import (
        enable_persistent_compilation_cache,
    )

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    calls = _record_config_updates(monkeypatch)
    assert enable_persistent_compilation_cache() == str(tmp_path / "jc")
    assert "jax_compilation_cache_dir" not in [name for name, _ in calls]
    assert not (tmp_path / "jc").exists()  # jax creates it, not us


def test_cache_dir_unset_resolves_inside_the_checkout(monkeypatch):
    from chiaswarm_tpu.core.compile_cache import (
        enable_persistent_compilation_cache,
    )

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = _record_config_updates(monkeypatch)
    want = str(REPO / ".jax_cache")
    assert enable_persistent_compilation_cache() == want
    assert ("jax_compilation_cache_dir", want) in calls


# ---- the worker sells TPU time: any other backend must be NAMED ---------


def test_worker_refuses_a_backend_nobody_named(tmp_path):
    """No JAX_PLATFORMS on a host without a TPU: jax falls back to the
    CPU by itself — the worker must refuse to start on it."""
    proc = subprocess.run(
        [sys.executable, "-m", "chiaswarm_tpu.cli", "worker"],
        env=_scrubbed_env(SWARM_TPU_ROOT=str(tmp_path),
                          SWARM_TPU_URI="http://127.0.0.1:9"),
        cwd=str(tmp_path), capture_output=True, text=True, timeout=180)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr


def test_worker_starts_on_an_explicitly_named_cpu(tmp_path):
    """``JAX_PLATFORMS=cpu`` is the dev-host mode: same command, starts
    (and is stopped by SIGTERM once it logs its backend)."""
    import signal
    import time

    proc = subprocess.Popen(
        [sys.executable, "-m", "chiaswarm_tpu.cli", "worker"],
        env=_scrubbed_env(SWARM_TPU_ROOT=str(tmp_path),
                          SWARM_TPU_URI="http://127.0.0.1:9",
                          JAX_PLATFORMS="cpu"),
        cwd=str(tmp_path), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    log_file = tmp_path / "logs" / "swarm-tpu.log"
    try:
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and proc.poll() is None:
            if log_file.exists() and "backend=cpu" in log_file.read_text():
                break
            time.sleep(0.2)
        assert proc.poll() is None, proc.stdout.read()
        assert "backend=cpu" in log_file.read_text()
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()


def test_hbm_budget_refuses_to_guess_a_tpu(monkeypatch):
    """``device_hbm_bytes``: the 16 GiB stand-in is for stat-less CPU
    test meshes only; a TPU that reports no ``bytes_limit`` raises."""
    from chiaswarm_tpu.core import mesh as mesh_mod

    class FakeDevice:
        def __init__(self, platform, stats):
            self.platform, self._stats = platform, stats

        def memory_stats(self):
            return self._stats

    assert mesh_mod.device_hbm_bytes(
        FakeDevice("cpu", None)) == mesh_mod._DEFAULT_HBM_BYTES
    assert mesh_mod.device_hbm_bytes(
        FakeDevice("tpu", {"bytes_limit": 123})) == 123
    with pytest.raises(RuntimeError, match="bytes_limit"):
        mesh_mod.device_hbm_bytes(FakeDevice("tpu", {}))

    # ... and the refusal reaches the residency ledger's constructor (the
    # only HBM consumer on a one-chip slot) instead of becoming a default
    import jax

    from chiaswarm_tpu.serving import residency

    monkeypatch.delenv("CHIASWARM_RESIDENCY_BUDGET", raising=False)
    monkeypatch.delenv("CHIASWARM_RESIDENCY_HARD_LIMIT", raising=False)
    monkeypatch.setattr(jax, "devices", lambda: [FakeDevice("tpu", {})])
    with pytest.raises(RuntimeError, match="bytes_limit"):
        residency.default_budget_bytes()
    with pytest.raises(RuntimeError, match="bytes_limit"):
        residency.default_hard_limit_bytes(1)
    with pytest.raises(RuntimeError, match="bytes_limit"):
        residency.ResidencyManager(persist_path=None)
    # an operator's explicit figures need no device to vouch for them
    manager = residency.ResidencyManager(budget_bytes=1000,
                                         hard_limit_bytes=2000,
                                         persist_path=None)
    assert manager.budget_bytes == 1000
