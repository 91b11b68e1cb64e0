"""R4 import-time-device-init: no backend initialization at module scope.

``jax.devices()`` / ``jax.device_count()`` / ``jax.default_backend()``
at import time pins the backend before the process has a chance to set
``JAX_PLATFORMS`` / distributed init (``tests/conftest.py`` sets both
before its first jax import). It also makes ``import chiaswarm_tpu.x``
require working accelerator plumbing, which breaks host-only tools and
the import-health test.

Module scope means anything executed at import: module body, class
bodies, decorator expressions, and default-argument values. Function and
lambda bodies only run when called and are fine.
"""

from __future__ import annotations

import ast
from typing import Iterator

from chiaswarm_tpu.analysis.core import Finding, ModuleContext, Rule, register
from chiaswarm_tpu.analysis.rules import resolves_to

_DEVICE_INIT = (
    "jax.devices",
    "jax.local_devices",
    "jax.device_count",
    "jax.local_device_count",
    "jax.default_backend",
    "jax.process_index",
    "jax.process_count",
    "jax.extend.backend.get_backend",
)


@register
class ImportTimeDeviceInit(Rule):
    code = "R4"
    name = "import-time-device-init"
    description = ("jax.devices()/device_count()/default_backend() must "
                   "not run at module import time")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        yield from self._visit(ctx, ctx.tree)

    def _visit(self, ctx: ModuleContext, node: ast.AST) -> Iterator[Finding]:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # decorators and default values DO execute at import
                for dec in child.decorator_list:
                    yield from self._scan_expr(ctx, dec)
                for default in (child.args.defaults
                                + [d for d in child.args.kw_defaults if d]):
                    yield from self._scan_expr(ctx, default)
                continue  # body runs at call time
            if isinstance(child, ast.Lambda):
                # the body runs at call time, but default values of a
                # module-scope lambda execute at import like a def's
                for default in (child.args.defaults
                                + [d for d in child.args.kw_defaults if d]):
                    yield from self._scan_expr(ctx, default)
                continue
            yield from self._visit(ctx, child)
            if isinstance(child, ast.Call):
                yield from self._check_call(ctx, child)

    def _scan_expr(self, ctx: ModuleContext,
                   expr: ast.AST) -> Iterator[Finding]:
        # manual walk: ast.walk would descend into Lambda bodies, which
        # do NOT execute at import time
        todo = [expr]
        while todo:
            node = todo.pop()
            if isinstance(node, ast.Lambda):
                continue
            if isinstance(node, ast.Call):
                yield from self._check_call(ctx, node)
            todo.extend(ast.iter_child_nodes(node))

    def _check_call(self, ctx: ModuleContext,
                    call: ast.Call) -> Iterator[Finding]:
        resolved = ctx.resolve_call(call)
        if resolves_to(resolved, *_DEVICE_INIT):
            yield self.finding(
                ctx, call,
                f"'{resolved}()' at module scope initializes the jax "
                f"backend at import time; defer it into the function that "
                f"needs it so JAX_PLATFORMS / distributed init still win")
