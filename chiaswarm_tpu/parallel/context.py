"""Trace-time mesh context: what `ops.attention` needs to know about
the mesh a program's params live on.

The reference has no sequence-parallel serving mode (its long-input answer
is single-GPU attention slicing, swarm/diffusion/diffusion_func.py:85-88).
Here, a pipeline whose params live on a mesh with a ``seq`` axis > 1 routes
its large self-attentions through `parallel.ring_attention` automatically:
the pipeline enters :func:`param_mesh` around its jitted program, and
`ops.attention` reads :func:`active_seq_mesh` at TRACE time to decide the
dispatch (a static decision — under `jax.jit` the context only needs to be
live during the first call that traces).

The same context carries ANY multi-device param mesh (:func:`active_mesh`):
GSPMD cannot partition a Mosaic kernel, so on a dp x tp slot the local flash
call must be wrapped in a ``shard_map`` over that mesh — the first run of a
four-chip default pool on the chip failed every SDXL job on exactly that.

A contextvar (not a global) so hermetic tests can run pipelines on
different meshes in one process without cross-talk.
"""

from __future__ import annotations

import contextlib
import contextvars

from jax.sharding import Mesh

from chiaswarm_tpu.core.mesh import SEQ_AXIS

_param_mesh: contextvars.ContextVar[Mesh | None] = contextvars.ContextVar(
    "chiaswarm_param_mesh", default=None)


def active_mesh() -> Mesh | None:
    """The multi-device mesh the program being traced keeps its params
    on, or None (single chip, or no context entered)."""
    mesh = _param_mesh.get()
    if mesh is not None and mesh.devices.size > 1:
        return mesh
    return None


def active_seq_mesh() -> Mesh | None:
    """The mesh whose ``seq`` axis should shard attention, or None.

    Returns None unless the context is entered AND the mesh actually has a
    ``seq`` axis of size > 1 — callers need no further checks."""
    mesh = _param_mesh.get()
    if mesh is not None and dict(mesh.shape).get(SEQ_AXIS, 1) > 1:
        return mesh
    return None


@contextlib.contextmanager
def param_mesh(mesh: Mesh | None):
    """Declare the mesh the traced program's params live on, so that
    qualifying attention routes over it: through the ring kinds when it
    has a ``seq`` axis > 1, through the shard_mapped flash kernel on any
    multi-device mesh.

    Entering with None (or a one-device mesh) is a no-op, so pipelines
    can wrap their programs unconditionally."""
    token = _param_mesh.set(mesh)
    try:
        yield
    finally:
        _param_mesh.reset(token)


def _mesh_of_params(params) -> Mesh | None:
    """The multi-device mesh ``params`` are placed on, or None."""
    import jax
    from jax.sharding import NamedSharding

    for leaf in jax.tree.leaves(params):
        s = getattr(leaf, "sharding", None)
        if isinstance(s, NamedSharding) and s.mesh.devices.size > 1:
            return s.mesh  # one placement per param tree; first leaf decides
    return None


@contextlib.contextmanager
def capture_ring_calls():
    """Observe ring_attention invocations (dryrun/test instrumentation):
    yields a list that accumulates each call's q shape.

    The package re-exports the function under its own name, so the real
    submodule is fetched via importlib (attribute-style ``import a.b as
    m`` would grab the function) and its attribute is swapped for the
    duration — ops.attention imports it at call time, so the swap is
    always observed."""
    import importlib

    mod = importlib.import_module("chiaswarm_tpu.parallel.ring_attention")
    calls: list = []
    real = mod.ring_attention

    def observing(*args, **kwargs):
        calls.append(args[0].shape)
        return real(*args, **kwargs)

    mod.ring_attention = observing
    try:
        yield calls
    finally:
        mod.ring_attention = real


def param_mesh_wrap(jitted, params):
    """Wrap a jitted pipeline program so it traces (and re-traces, after
    executable-LRU rebuilds) under :func:`param_mesh` whenever ``params``
    live on a multi-device mesh — the single hook every pipeline uses to
    make ring attention (seq > 1) and the shard_map'd flash kernel (any
    dp x tp x sp mesh) serving paths rather than demos. Single-chip
    callers get the jitted fn back untouched (zero overhead on the
    common path)."""
    mesh = _mesh_of_params(params)
    if mesh is None:
        return jitted

    def wrapped(*args):
        with param_mesh(mesh):
            return jitted(*args)

    return wrapped
