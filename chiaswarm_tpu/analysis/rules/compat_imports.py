"""R3 compat-import: JAX API churn crosses through core/compat.py only.

The repo runs on one jax (0.9.0, floored in pyproject.toml), but JAX
moves public surface between minors (``shard_map`` graduated out of
experimental, ``lax.axis_size`` is young, ...). The seed paid for this
twice under an older jax: ``from jax import shard_map`` in a test
poisoned the whole tier-1 collection, and ``lax.axis_size`` broke every
sequence-parallel path at runtime. Keeping every such import in ONE file
makes the next bump a one-file edit.

Policy, driven by the table in ``chiaswarm_tpu/core/compat.py``:

- importing a symbol listed in ``COMPAT_TABLE`` (e.g. ``from jax import
  shard_map``, ``from jax.experimental.shard_map import shard_map``) is a
  finding anywhere outside compat.py — even inside try/except, because
  every hand-rolled fallback is one more site to migrate on the next
  bump;
- calling an attribute path listed there (``jax.lax.axis_size(...)``) is
  likewise a finding;
- any other ``jax.experimental.*`` import must be either in
  ``ALLOWED_EXPERIMENTAL`` or guarded by try/except ImportError — the
  experimental namespace carries no stability promise.
"""

from __future__ import annotations

import ast
import sys
from typing import Iterator

from chiaswarm_tpu.analysis.core import Finding, ModuleContext, Rule, register


def _load_compat():
    """The compat table, WITHOUT importing chiaswarm_tpu.core.

    ``chiaswarm_tpu/core/__init__.py`` imports jax; the linter must stay
    stdlib-only AND seconds-fast (it runs in CI jobs and hooks with no
    jax installed), so load compat.py directly by path — never through
    the package, which would drag in the whole jax runtime."""
    if "chiaswarm_tpu.core.compat" in sys.modules:
        return sys.modules["chiaswarm_tpu.core.compat"]
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), "core", "compat.py")
    spec = importlib.util.spec_from_file_location(
        "_swarmlint_compat", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses resolves __module__
    spec.loader.exec_module(mod)
    return mod


_COMPAT = _load_compat()
ALLOWED_EXPERIMENTAL = _COMPAT.ALLOWED_EXPERIMENTAL
COMPAT_TABLE = _COMPAT.COMPAT_TABLE

_EXEMPT_SUFFIX = "chiaswarm_tpu/core/compat.py"
_FORBIDDEN_CALLS = {key.replace(":", "."): entry
                    for key, entry in COMPAT_TABLE.items()}


def _experimental_allowed(module: str) -> bool:
    return any(module == allowed or module.startswith(allowed + ".")
               for allowed in ALLOWED_EXPERIMENTAL)


@register
class CompatImport(Rule):
    code = "R3"
    name = "compat-import"
    description = ("version-sensitive jax imports must route through "
                   "chiaswarm_tpu.core.compat (the jax crossing-point "
                   "table)")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        if ctx.relpath.endswith(_EXEMPT_SUFFIX):
            return
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ImportFrom) and node.module:
                yield from self._check_import_from(ctx, node)
            elif isinstance(node, ast.Import):
                yield from self._check_import(ctx, node)
            elif isinstance(node, ast.Call):
                resolved = ctx.resolve_call(node)
                if resolved in _FORBIDDEN_CALLS:
                    entry = _FORBIDDEN_CALLS[resolved]
                    yield self.finding(
                        ctx, node,
                        f"'{resolved}' is version-sensitive; use "
                        f"chiaswarm_tpu.core.compat.{entry.symbol} "
                        f"({entry.note})")

    def _check_import_from(self, ctx: ModuleContext,
                           node: ast.ImportFrom) -> Iterator[Finding]:
        module = node.module or ""
        for alias in node.names:
            key = f"{module}:{alias.name}"
            if key in COMPAT_TABLE:
                entry = COMPAT_TABLE[key]
                yield self.finding(
                    ctx, node,
                    f"'from {module} import {alias.name}' is version-"
                    f"sensitive (installed jax: {entry.path}); import "
                    f"chiaswarm_tpu.core.compat.{entry.symbol} instead")
                continue
            if module.startswith("jax.experimental"):
                # `from jax.experimental import pallas` targets the
                # pallas SUBMODULE — judge the full dotted path
                yield from self._check_experimental(
                    ctx, node, f"{module}.{alias.name}")

    def _check_import(self, ctx: ModuleContext,
                      node: ast.Import) -> Iterator[Finding]:
        for alias in node.names:
            if alias.name.startswith("jax.experimental"):
                yield from self._check_experimental(ctx, node, alias.name)

    def _check_experimental(self, ctx: ModuleContext, node: ast.AST,
                            module: str) -> Iterator[Finding]:
        if _experimental_allowed(module):
            return
        if ctx.in_import_guard(node):
            return
        yield self.finding(
            ctx, node,
            f"unguarded '{module}' import: jax.experimental carries no "
            f"stability promise across versions — wrap in try/except "
            f"ImportError, or add a shim to chiaswarm_tpu.core.compat "
            f"(allowed without a guard: "
            f"{', '.join(sorted(ALLOWED_EXPERIMENTAL))})")
