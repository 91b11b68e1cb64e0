"""hlocheck — compiled-program contract checker (swarmproof, compiled side).

``analysis/shardflow.py`` proves sharding value-semantics over *source*;
this module audits what GSPMD/XLA actually *lowered*, because the r06
divergence family is precisely a case where correct-looking source
compiles to a wrong collective: an ``all-reduce`` over an
already-complete product is invisible in Python and one grep away in the
scheduled HLO. Reuses ``obs/hlocost.py``'s HLO walker — pure stdlib,
text in, facts out, no jax import (callers that *build* programs, like
``tools/shard_audit.py``, bring their own).

Three checks against a declared per-program **contract** (JSON):

- **collective budget** — observed collective counts by op
  (``all-reduce`` / ``all-gather`` / ``reduce-scatter`` /
  ``collective-permute`` / ``all-to-all``, async ``-start`` forms folded
  in, ``-done`` halves skipped) vs ``{"collectives": {op: {"min", "max"},
  "max_total": n}}``. An unexpected ``all-reduce`` in a ring program is
  the runtime face of R11 ``replicated-psum``; a missing
  ``collective-permute`` means the ring never lowered at all.
- **dtype drift** — matmul/conv result-dtype census vs
  ``{"dtype": {"forbid": ["f32"], "allow_ops": n}}``: f32 upcasts inside
  a bf16 program burn double HBM and MXU throughput silently.
- **donation** — declared donated parameter indices vs the lowered
  ``input_output_alias`` table (``{"donation": {"require_params": [...]}}``):
  XLA silently DROPS donation on layout/sharding mismatch, which is rule
  R13 ``donation-drift``'s compiled face — the buffer the source
  promised to reuse quietly doubles peak HBM.

Every absent contract key is record-only: :func:`census` always reports
the observed facts, and CI pins only
what is stable on the host it runs on (donation is not implemented on
CPU backends, so the CPU contract pins collectives and dtype, and
records donation).
"""

from __future__ import annotations

import re
from typing import Any

from chiaswarm_tpu.obs.hlocost import (
    _SHAPE_RE,
    iter_instruction_lines,
)

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all")

_COLLECTIVE_RE = re.compile(
    r"\b(" + "|".join(COLLECTIVE_OPS) + r")(-start)?\(")
_MATMUL_RE = re.compile(
    r"=\s*(" + _SHAPE_RE.pattern + r")[^=]*?\b(convolution|dot)\(")
#: the alias table nests exactly one level ({output index}: (param, {}))
_ALIAS_BLOCK_RE = re.compile(
    r"input_output_alias=\{((?:[^{}]|\{[^{}]*\})*)\}")
_ALIAS_PARAM_RE = re.compile(r"\(\s*(\d+)\s*[,)]")
_REPLICA_GROUPS_RE = re.compile(
    r"replica_groups=(\{\{[\d,{}\s]*\}\}|\[\d+,\d+\]<=\[[\d,]+\])")


# ---------------------------------------------------------------------------
# census: observed facts of one lowered program


def collective_census(text: str) -> dict[str, dict]:
    """op -> {"count", "group_sizes"} over a scheduled-HLO dump. Async
    pairs count once (the ``-start``; the ``-done`` carries no new
    collective). ``group_sizes`` are the replica-group sizes seen — the
    static fingerprint of WHICH mesh axis a collective runs over (a
    ``seq``=4 axis shows groups of 4)."""
    out: dict[str, dict] = {}
    for _, line in iter_instruction_lines(text):
        m = _COLLECTIVE_RE.search(line)
        if not m:
            continue
        entry = out.setdefault(m.group(1),
                               {"count": 0, "group_sizes": []})
        entry["count"] += 1
        g = _REPLICA_GROUPS_RE.search(line)
        if g:
            size = _group_size(g.group(1))
            if size and size not in entry["group_sizes"]:
                entry["group_sizes"].append(size)
    for entry in out.values():
        entry["group_sizes"].sort()
    return out


def _group_size(spec: str) -> int | None:
    if spec.startswith("{{"):
        first = spec[2:].split("}", 1)[0]
        ids = [t for t in first.split(",") if t.strip() != ""]
        return len(ids) or None
    m = re.match(r"\[(\d+),(\d+)\]<=", spec)  # iota form: G groups of S
    if m:
        return int(m.group(2))
    return None


def matmul_dtype_census(text: str) -> dict[str, int]:
    """Result-dtype histogram of every convolution/dot instruction
    (fused computations included — an f32 dot inside a fusion is still
    f32 MXU work)."""
    out: dict[str, int] = {}
    for _, line in iter_instruction_lines(text):
        m = _MATMUL_RE.search(line)
        if m:
            dtype = _SHAPE_RE.search(m.group(1)).group(1)
            out[dtype] = out.get(dtype, 0) + 1
    return out


def donated_param_indices(text: str) -> list[int]:
    """Parameter indices the lowered program actually aliases to outputs
    (the ``input_output_alias`` table on the HloModule line) — what
    XLA *kept* of the source's donation declarations."""
    m = _ALIAS_BLOCK_RE.search(text)
    if not m:
        return []
    return sorted({int(p) for p in _ALIAS_PARAM_RE.findall(m.group(1))})


def census(text: str) -> dict[str, Any]:
    """All observed contract-relevant facts of one program — the
    record-only half of an audit."""
    return {
        "collectives": collective_census(text),
        "matmul_dtypes": matmul_dtype_census(text),
        "donated_params": donated_param_indices(text),
    }


# ---------------------------------------------------------------------------
# audit: observed facts vs a declared contract


def audit_hlo(text: str, contract: dict,
              program: str = "program",
              obs: dict | None = None) -> list[dict]:
    """Violations of ``contract`` by one lowered program. Each violation
    is ``{"check", "rule", "program", "message"}`` — ``rule`` names the
    swarmlint rule whose runtime face the violation is (R11
    ``replicated-psum`` for collective overruns, R13 ``donation-drift``
    for dropped donation, ``dtype-drift`` for precision upcasts). Pass a
    precomputed ``obs`` (:func:`census` output) to skip re-walking the
    text — real UNet dumps are tens of MB."""
    violations: list[dict] = []
    if obs is None:
        obs = census(text)

    budget = contract.get("collectives") or {}
    total = sum(e["count"] for e in obs["collectives"].values())
    if "max_total" in budget and total > budget["max_total"]:
        ops = ", ".join(f"{op} x{e['count']}"
                        for op, e in sorted(obs["collectives"].items()))
        violations.append({
            "check": "collective-budget", "rule": "replicated-psum",
            "program": program,
            "message": (f"{total} collective(s) lowered "
                        f"({ops or 'none'}) but the contract allows at "
                        f"most {budget['max_total']} — an unexpected "
                        f"all-reduce over a complete product is the "
                        f"runtime face of R11"),
        })
    for op, limits in budget.items():
        if op == "max_total" or not isinstance(limits, dict):
            continue
        got = obs["collectives"].get(op, {}).get("count", 0)
        if "max" in limits and got > limits["max"]:
            violations.append({
                "check": "collective-budget", "rule": "replicated-psum",
                "program": program,
                "message": (f"{got} {op}(s) lowered but the contract "
                            f"allows at most {limits['max']}"),
            })
        if "min" in limits and got < limits["min"]:
            violations.append({
                "check": "collective-budget", "rule": "replicated-psum",
                "program": program,
                "message": (f"only {got} {op}(s) lowered but the "
                            f"contract requires at least "
                            f"{limits['min']} — the collective the "
                            f"program is built around never made it "
                            f"into the executable"),
            })

    dtype = contract.get("dtype") or {}
    allow = int(dtype.get("allow_ops", 0))
    for forbidden in dtype.get("forbid", ()):
        got = obs["matmul_dtypes"].get(forbidden, 0)
        if got > allow:
            violations.append({
                "check": "dtype-drift", "rule": "dtype-drift",
                "program": program,
                "message": (f"{got} {forbidden} matmul/conv op(s) in a "
                            f"program contracted to forbid {forbidden} "
                            f"(allow_ops={allow}) — silent precision "
                            f"upcast doubles HBM traffic and halves "
                            f"MXU throughput"),
            })

    donation = contract.get("donation") or {}
    required = donation.get("require_params", [])
    missing = sorted(set(required) - set(obs["donated_params"]))
    if missing:
        violations.append({
            "check": "donation", "rule": "donation-drift",
            "program": program,
            "message": (f"parameter(s) {missing} declared donated but "
                        f"the lowered program's input_output_alias "
                        f"table does not alias them — XLA dropped the "
                        f"donation (layout/sharding mismatch), peak "
                        f"HBM silently doubles (R13's compiled face)"),
        })
    return violations


def audit_programs(programs: dict[str, str],
                   contract: dict) -> dict[str, Any]:
    """Audit a set of named programs against a contract file of the
    shape ``{"programs": {name: {…}}}``; unknown program names audit
    against an empty (record-only) contract."""
    per = contract.get("programs") or {}
    report: dict[str, Any] = {"programs": {}, "violations": []}
    for name, text in sorted(programs.items()):
        obs = census(text)
        report["programs"][name] = obs
        report["violations"].extend(
            audit_hlo(text, per.get(name) or {}, program=name, obs=obs))
    report["ok"] = not report["violations"]
    return report
