"""Hermetic test config: force an 8-device CPU platform BEFORE jax imports,
so multi-chip mesh/sharding code is exercised without a TPU (SURVEY.md §4)."""

import os
import tempfile

# session-level settings-root isolation: the process-global residency
# manager (serving/residency.py, ISSUE 8) persists measured footprints
# under settings_root() at its FIRST registry construction — without
# this default, any test building a ModelRegistry before a per-test
# SWARM_TPU_ROOT fixture runs would write tiny/random-model footprints
# into the operator's real ~/.swarm-tpu/residency.json. Tests that set
# their own root (monkeypatch.setenv) still override per-test.
os.environ.setdefault(
    "SWARM_TPU_ROOT", tempfile.mkdtemp(prefix="swarm-tpu-test-root-"))

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

SUITE_MATMUL_PRECISION = "float32"
jax.config.update("jax_default_matmul_precision", SUITE_MATMUL_PRECISION)

# persistent XLA compile cache: the suite is compile-bound; warm reruns
# skip most of that. Same placement rule as every entry point
# (core/compile_cache.py): JAX_COMPILATION_CACHE_DIR when set, else
# <checkout>/.jax_cache
from chiaswarm_tpu.core.compile_cache import (  # noqa: E402
    enable_persistent_compilation_cache,
)

enable_persistent_compilation_cache()
# the suite is dominated by many SMALL compiles (tiny families, one
# program per test parameterization) — persist nearly all of them, not
# just the >2s ones the serving default targets
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _restore_matmul_precision():
    """``Worker.run()`` pins bf16 matmuls for its process (node/worker.py),
    and a test's worker shares this one: put the suite's precision back
    after every test, so that what a later test of the same xdist worker
    traces or computes does not depend on which file ran before it."""
    yield
    jax.config.update("jax_default_matmul_precision",
                      SUITE_MATMUL_PRECISION)


# ---- fast / slow tiers (VERDICT r3 weak #4) ---------------------------
# Default `pytest -q` runs the fast tier; the ~10 compile-heaviest tests
# are marked `slow` and run with --slow (or CHIASWARM_SLOW=1) — the
# nightly-CI tier (.github/workflows/test.yml).


def pytest_addoption(parser):
    parser.addoption(
        "--slow", action="store_true", default=False,
        help="also run tests marked slow (full tier; nightly CI)")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: compile-heavy test, excluded from the default fast tier "
        "(run with --slow or CHIASWARM_SLOW=1)")
    config.addinivalue_line(
        "markers",
        "solo: exercises the per-job (non-lane) path — the CI "
        "stepper-off leg re-runs this subset with CHIASWARM_STEPPER=0")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--slow") or os.environ.get("CHIASWARM_SLOW"):
        return
    skip = pytest.mark.skip(
        reason="slow tier: run with --slow or CHIASWARM_SLOW=1")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(scope="session")
def cpu_devices():
    return jax.devices("cpu")


@pytest.fixture(scope="session")
def mesh8():
    from chiaswarm_tpu.core.mesh import MeshSpec, build_mesh

    return build_mesh(MeshSpec({"data": 4, "model": 2}))
