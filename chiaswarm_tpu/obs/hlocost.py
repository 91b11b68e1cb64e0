"""Static HLO parsing and program capture (swarmlens, ISSUE 11).

Two layers, both with a caller outside this module:

- **parsing/costing** — :func:`parse_hlo_text` statically costs every
  fusion / bare conv / dot / flash custom-call in a scheduled-HLO dump:
  conv FLOPs from window/dim_labels/feature_group_count, dot FLOPs from
  contracting dims, flash FLOPs from the folded (B*H, L, D) operands,
  HBM bytes as operands+result touched once (``chip_smoke.py`` counts
  the flash custom calls of every lane step program with it).
  :func:`iter_instruction_lines` is the one HLO walker; the contract
  checker (``analysis/hlocheck.py``) walks HLO through it too.
- **program capture** — :func:`compiled_hlo_text` and
  :class:`ProgramCapture` (``tools/shard_audit.py``,
  ``tools/key_audit.py``, ``chip_smoke.py``).

This module joins nothing to a clock and knows no peaks: roofline
shares are the benchmark's (``perfbench/hlo.py`` with
``perfbench/peaks.json``, by ``device_kind``).
Pure stdlib at import (jax only inside :class:`ProgramCapture`), like
the rest of ``obs/``.
"""

from __future__ import annotations

import math
import re
from typing import Any, Callable, Iterable

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
}

_SHAPE_RE = re.compile(r"\b(pred|[su]\d+|bf16|f16|f32|f64)\[([\d,]*)\]")
_NAME_RE = re.compile(r"%([\w.-]+)")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.-]+)\s*=\s*(.+)$")


def _shape_dims(dtype_dims: tuple[str, str]):
    dtype, dims = dtype_dims
    return dtype, [int(d) for d in dims.split(",") if d]


def _shape_bytes(dtype: str, dims: list[int]) -> int:
    return math.prod(dims, start=1) * _DTYPE_BYTES.get(dtype, 4)


def build_shape_map(text: str) -> dict[str, tuple[str, list[int]]]:
    """instruction name -> (dtype, dims) of its (first) result shape.

    Scheduled HLO prints operands as bare ``%names`` (no inline shapes),
    so operand shapes must be resolved through the defining instruction.
    """
    shape_map: dict[str, tuple[str, list[int]]] = {}
    for line in text.splitlines():
        d = _DEF_RE.match(line)
        if not d:
            continue
        m = _SHAPE_RE.search(d.group(2))
        if m:
            shape_map[d.group(1)] = _shape_dims(m.groups())
    return shape_map


def operand_shapes(line: str, opcode: str,
                   shape_map) -> list[tuple[str, list[int]]]:
    """(dtype, dims) of each operand of ``opcode`` on ``line`` — inline
    shapes when the printer emitted them, the definition map otherwise."""
    start = line.find(opcode + "(")
    if start < 0:
        return []
    seg = line[start + len(opcode) + 1:]
    # the operand list ends at the first ")" outside {} layout braces and
    # outside nested "(" groups (tuple-typed inline shapes)
    brace = paren = 0
    end = len(seg)
    for i, ch in enumerate(seg):
        if ch == "{":
            brace += 1
        elif ch == "}":
            brace -= 1
        elif brace == 0 and ch == "(":
            paren += 1
        elif brace == 0 and ch == ")":
            if paren:
                paren -= 1
            else:
                end = i
                break
    seg = seg[:end]
    inline = _SHAPE_RE.findall(seg)
    names = _NAME_RE.findall(seg)
    if inline and len(inline) >= len(names):
        return [_shape_dims(s) for s in inline]
    return [shape_map[n] for n in names if n in shape_map]


def conv_flops(line: str, shape_map) -> float:
    """FLOPs of one HLO convolution instruction (per execution):
    2 * out_elems * window_elems * in_features / feature_group_count."""
    m = _SHAPE_RE.search(line.split("=", 1)[-1])
    if not m:
        return 0.0
    _, out_dims = _shape_dims(m.groups())
    out_elems = math.prod(out_dims, start=1)

    window = re.search(r"window={[^}]*?size=([\dx]+)", line)
    window_elems = 1
    if window:
        for d in window.group(1).split("x"):
            window_elems *= int(d)

    labels = re.search(r"dim_labels=(\S+?)->", line)
    groups = re.search(r"feature_group_count=(\d+)", line)
    group_n = int(groups.group(1)) if groups else 1

    in_features = 1
    operands = operand_shapes(line, "convolution", shape_map)
    if labels and len(operands) >= 2:
        lhs_rhs = labels.group(1).split("_")
        if len(lhs_rhs) == 2:
            rhs_spec = lhs_rhs[1]  # e.g. "01io"
            rhs_dims = operands[1][1]
            i_pos = rhs_spec.find("i")
            if 0 <= i_pos < len(rhs_dims):
                in_features = rhs_dims[i_pos]
    return 2.0 * out_elems * window_elems * in_features / group_n


def dot_flops(line: str, shape_map) -> float:
    """FLOPs of one HLO dot: 2 * out_elems * prod(contracting dims)."""
    m = _SHAPE_RE.search(line.split("=", 1)[-1])
    if not m:
        return 0.0
    _, out_dims = _shape_dims(m.groups())
    out_elems = math.prod(out_dims, start=1)
    contract = re.search(r"lhs_contracting_dims={([\d,]*)}", line)
    operands = operand_shapes(line, "dot", shape_map)
    k = 1
    if contract and contract.group(1) and operands:
        lhs_dims = operands[0][1]
        for idx in contract.group(1).split(","):
            i = int(idx)
            if i < len(lhs_dims):
                k *= lhs_dims[i]
    return 2.0 * out_elems * k


def flash_flops(line: str, shape_map) -> float:
    """Attention FLOPs of a flash custom call: 2*BH*L*S*D for QK^T plus
    the same for PV — 4*BH*L*S*D. The kernel folds heads into the lead
    dim and pads L/S to its block lattice, so operands are
    (B*H, L_pad, D) (ops/flash_attention.py) — padded work is real
    compute and is costed as such."""
    operands = [dims for _, dims in
                operand_shapes(line, "custom-call", shape_map)
                if len(dims) == 3]
    if len(operands) < 2:
        return 0.0
    bh, l, d = operands[0]
    s = operands[1][1]
    return 4.0 * bh * l * s * d


def io_bytes(line: str, opcode: str, shape_map) -> int:
    """HBM traffic estimate of one instruction: result + operand shapes,
    each touched once."""
    total = 0
    m = _SHAPE_RE.search(line.split("=", 1)[-1])
    if m:
        total += _shape_bytes(*_shape_dims(m.groups()))
    for dtype, dims in operand_shapes(line, opcode, shape_map):
        total += _shape_bytes(dtype, dims)
    return total


_COMP_HEADER_RE = re.compile(
    r"\s*(?:ENTRY\s+)?%?([\w.-]+)\s+\(.*\)\s*->\s*.+\{\s*$")


def iter_instruction_lines(text: str):
    """Yield (computation name, raw line) for every instruction line in
    an HLO dump ("" at module scope). The one place the computation
    bracketing logic lives — :func:`parse_hlo_text` and the contract
    checker (``analysis/hlocheck.py``) both walk HLO through it."""
    current = None
    for line in text.splitlines():
        header = _COMP_HEADER_RE.match(line)
        if header:
            current = header.group(1)
            continue
        if line.startswith("}"):
            current = None
            continue
        yield (current or ""), line


def parse_hlo_text(text: str) -> dict[str, dict]:
    """fusion/conv/dot name -> {flops, bytes, kind, computation} from
    scheduled HLO. ``computation`` is the enclosing computation name
    ("" at module scope)."""
    shape_map = build_shape_map(text)

    # computation name -> [total conv+dot flops inside it, kind]
    comp_flops: dict[str, list] = {}
    for current, line in iter_instruction_lines(text):
        if not current:
            continue
        if " convolution(" in line:
            entry = comp_flops.setdefault(current, [0.0, "conv"])
            entry[0] += conv_flops(line, shape_map)
        elif re.search(r"\bdot\(", line):
            entry = comp_flops.setdefault(current, [0.0, "dot"])
            entry[0] += dot_flops(line, shape_map)
            if entry[1] == "conv":
                entry[1] = "mixed"

    fusions: dict[str, dict] = {}
    for comp, line in iter_instruction_lines(text):
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.-]+)\s*=\s*.*?\bfusion\(",
                     line)
        if not m:
            # bare convs/dots outside fusions still deserve a row
            b = re.match(
                r"\s*(?:ROOT\s+)?%?([\w.-]+)\s*=\s*.*?\b"
                r"(convolution|dot)\(", line)
            if b:
                op = b.group(2)
                flops = (conv_flops(line, shape_map)
                         if op == "convolution"
                         else dot_flops(line, shape_map))
                fusions[b.group(1)] = {
                    "flops": flops,
                    "bytes": io_bytes(line, op, shape_map),
                    "kind": "conv" if op == "convolution" else "dot",
                    "computation": comp}
            elif "custom-call" in line and "flash_attention" in line:
                c = re.match(r"\s*(?:ROOT\s+)?%?([\w.-]+)\s*=", line)
                if c:
                    fusions[c.group(1)] = {
                        "flops": flash_flops(line, shape_map),
                        "bytes": io_bytes(line, "custom-call", shape_map),
                        "kind": "flash",
                        "computation": comp}
            continue
        name = m.group(1)
        called = re.search(r"calls=%?([\w.-]+)", line)
        flops, kind = 0.0, "other"
        if called and called.group(1) in comp_flops:
            flops, kind = comp_flops[called.group(1)]
        # HBM traffic estimate: every operand + the result, touched once
        # (fusions stream operands from HBM exactly once)
        fusions[name] = {"flops": flops,
                         "bytes": io_bytes(line, "fusion", shape_map),
                         "kind": kind,
                         "computation": comp}
    return fusions


# ---------------------------------------------------------------------------
# program capture (the AOT-compile seam of the audit tools)
# ---------------------------------------------------------------------------


def compiled_hlo_text(compiled: Any) -> str:
    """Post-optimization HLO of a jax Compiled object, across backends:
    CPU exposes ``as_text``; the TPU plugin's scheduled HLO comes from
    ``runtime_executable().get_hlo_text()`` (the exact text the chip
    runs)."""
    runtime = getattr(compiled, "runtime_executable", None)
    if callable(runtime):
        try:
            return runtime().get_hlo_text()
        except Exception:
            pass
    return compiled.as_text()


class ProgramCapture:
    """AOT-capturing stand-in for ``toplevel_jit``: patch it into a
    pipeline module so every top-level program the pipeline builds is
    compiled via ``.lower().compile()`` and its executable is kept for
    HLO extraction. Executables are keyed per input-shape signature, so
    a wrapper reused across shapes (stepper lattice programs) recompiles
    per signature exactly like the real jit would.

    Usage::

        cap = ProgramCapture()
        with cap.patching(diffusion_mod):
            pipe(req)                       # compile + run as usual
        hlo = cap.largest_hlo()             # the generate program
    """

    def __init__(self, real_toplevel_jit: Callable | None = None) -> None:
        if real_toplevel_jit is None:
            from chiaswarm_tpu.core.compile_cache import toplevel_jit
            real_toplevel_jit = toplevel_jit
        self._real = real_toplevel_jit
        self.executables: list[Any] = []

    def capturing_toplevel_jit(self, fn, **kwargs):
        jitted = self._real(fn, **kwargs)
        compiled_by_sig: dict[tuple, Any] = {}

        def signature(args):
            return tuple(
                (getattr(a, "shape", None), str(getattr(a, "dtype", "")))
                if hasattr(a, "shape") else type(a).__name__
                for a in args)

        def wrapper(*args):
            sig = signature(args)
            compiled = compiled_by_sig.get(sig)
            if compiled is None:
                compiled = jitted.lower(*args).compile()
                compiled_by_sig[sig] = compiled
                self.executables.append(compiled)
            return compiled(*args)

        return wrapper

    def patching(self, *modules):
        """Context manager: swap each module's ``toplevel_jit`` binding
        for the capturing wrapper (pipelines import the NAME, so the
        module attribute — not compile_cache — is what must change)."""
        import contextlib

        @contextlib.contextmanager
        def cm():
            saved = [(m, m.toplevel_jit) for m in modules]
            for m in modules:
                m.toplevel_jit = self.capturing_toplevel_jit
            try:
                yield self
            finally:
                for m, real in saved:
                    m.toplevel_jit = real

        return cm()

    def largest_hlo(self, executables: Iterable[Any] | None = None) -> str | None:
        """The longest HLO text among captured executables — in a
        pipeline build that is the end-to-end generate program."""
        pool = list(self.executables if executables is None
                    else executables)
        texts = []
        for compiled in pool:
            try:
                texts.append(compiled_hlo_text(compiled))
            except Exception:
                continue
        return max(texts, key=len) if texts else None
