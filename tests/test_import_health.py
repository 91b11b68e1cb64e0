"""Import health: every chiaswarm_tpu module imports cleanly on CPU.

API-churn breakage (a symbol that does not exist on the installed jax, an
import-time device query, a missing optional dep used unguarded) should
fail ONE named test per module — not poison the whole pytest collection
the way the seed's ``from jax import shard_map`` did. The static pass
(tests/test_lint.py) catches the known patterns; this test catches the
unknown ones by simply importing everything.

Runs under the suite's JAX_PLATFORMS=cpu conftest; modules must import
without an accelerator (R4 import-time-device-init is the static half of
the same invariant).
"""

from __future__ import annotations

import importlib
import pkgutil

import pytest

import chiaswarm_tpu


def _all_modules() -> list[str]:
    names = ["chiaswarm_tpu"]
    # a subpackage whose __init__ fails to import would otherwise be
    # silently SKIPPED by walk_packages (its submodules vanish from the
    # suite); record it so it still fails a named test below
    for info in pkgutil.walk_packages(chiaswarm_tpu.__path__,
                                      prefix="chiaswarm_tpu.",
                                      onerror=names.append):
        if info.name.rsplit(".", 1)[-1] == "__main__":
            continue  # CLI entry modules are exercised via subprocess tests
        names.append(info.name)
    return sorted(names)


_MODULES = _all_modules()


def test_module_walk_sees_the_whole_package():
    # a packaging regression that hides subpackages from pkgutil would
    # silently shrink this suite; pin a floor near the current count (88)
    assert len(_MODULES) >= 85, _MODULES


@pytest.mark.parametrize("name", _MODULES)
def test_imports_cleanly(name: str):
    importlib.import_module(name)
