"""key_audit — prove the executable-cache key tracks every trace knob.

The operator face of swarmkey's compiled side (analysis/keyflow.py): for
every trace-affecting env knob in ``compile_cache._TRACE_ENV_KNOBS``,
build the real tiny attention programs with the knob unset and set, and
assert **executable identity changes iff the key changes** — flipping a
knob must produce a different ``static_cache_key`` (so a warm slot can
never serve the stale program), and with every knob at its default the
key must be byte-identical to the historical 3-tuple (so default
deployments keep every warm slot: the taps-off stance from ISSUE 11,
generalized from one byte-identical-HLO gate into a sweep).

Each probe runs in a SUBPROCESS with a scrubbed ``CHIASWARM_*``
environment plus the scenario's overrides — the flash block/VMEM knobs
are frozen into module constants at import, so flipping them inside one
process would silently audit the stale constants (R18's import-time
face, turned on the audit itself).

Programs (all CPU-hermetic, 8 virtual devices, interpret-mode Pallas):

- ``local``     jitted ``ops.attention`` at l=64 — the einsum path by
                default; ``CHIASWARM_ATTENTION=flash`` swaps in the
                interpret-mode flash kernel (different HLO).
- ``ringmesh``  the same call traced under a seq=4 mesh
                (``parallel.context.param_mesh``) — local einsum
                by default (l=64 is under the ring threshold);
                ``CHIASWARM_RING_MIN_TOKENS=16`` engages the ppermute
                ring (different HLO).
- ``flash``     explicit ``impl="flash"`` — block knobs change the
                interpret-mode grid (different HLO).
- ``none``      key/fingerprint only, no build — for knobs whose HLO
                effect is TPU-only (ring-flash mode selects the fused
                kernel only on TPU; the VMEM cap and XLA compiler
                options only apply to non-interpret TPU lowering). On
                CPU these assert the KEY changes and the HLO does NOT —
                the key is deliberately a superset of what this host
                can observe.

Exit codes: 0 = every knob keyed and program-sensitive as declared ·
1 = violations (an unkeyed knob or an unexplained program change) ·
2 = probe/build error.
"""

from __future__ import annotations

import argparse
import ast
import hashlib
import json
import os
import subprocess
import sys


def _ensure_env() -> None:
    """Mirror tests/conftest.py on CPU hosts: a virtual 8-device
    platform, set BEFORE jax imports (same stance as shard_audit.py)."""
    if os.environ.get("JAX_PLATFORMS", "") in ("", "cpu"):
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()


# ---------------------------------------------------------------------------
# probe side (subprocess): build one program, report key + HLO identity


def _probe_args():
    import jax.numpy as jnp

    b, l, h, d = 2, 64, 2, 16
    return [jnp.linspace(0.0, 1.0, b * l * h * d,
                         dtype=jnp.float32).reshape(b, l, h, d)
            for _ in range(3)]


def _hlo_local() -> str:
    import jax

    from chiaswarm_tpu.obs.hlocost import compiled_hlo_text
    from chiaswarm_tpu.ops.attention import attention

    def f(q, k, v):
        return attention(q, k, v)

    return compiled_hlo_text(jax.jit(f).lower(*_probe_args()).compile())


def _hlo_ringmesh() -> str:
    import jax

    from chiaswarm_tpu.core.mesh import MeshSpec, build_mesh
    from chiaswarm_tpu.obs.hlocost import compiled_hlo_text
    from chiaswarm_tpu.ops.attention import attention
    from chiaswarm_tpu.parallel.context import param_mesh

    mesh = build_mesh(MeshSpec({"seq": 4}), devices=jax.devices()[:4])

    def f(q, k, v):
        return attention(q, k, v)

    with param_mesh(mesh):  # dispatch resolves at TRACE time
        compiled = jax.jit(f).lower(*_probe_args()).compile()
    return compiled_hlo_text(compiled)


def _hlo_flash() -> str:
    import jax

    from chiaswarm_tpu.obs.hlocost import compiled_hlo_text
    from chiaswarm_tpu.ops.attention import attention

    def f(q, k, v):
        return attention(q, k, v, impl="flash")

    return compiled_hlo_text(jax.jit(f).lower(*_probe_args()).compile())


_PROGRAMS = {
    "local": _hlo_local,
    "ringmesh": _hlo_ringmesh,
    "flash": _hlo_flash,
}


def run_probe(program: str) -> int:
    _ensure_env()
    from chiaswarm_tpu.core.compile_cache import (
        cache_fingerprint, static_cache_key,
    )

    out = {
        "key": repr(static_cache_key(0, "audit", {"l": 64})),
        "fingerprint": repr(cache_fingerprint()),
        "hlo_sha": None,
    }
    if program != "none":
        hlo = _PROGRAMS[program]()
        out["hlo_sha"] = hashlib.sha256(hlo.encode()).hexdigest()
    print(json.dumps(out))
    return 0


# ---------------------------------------------------------------------------
# audit side (parent): scenario sweep over scrubbed subprocess probes

#: knob -> (program, override value, hlo_changes_on_cpu). A False third
#: field documents a TPU-only HLO effect: the key must still change (the
#: key is a superset of what CPU can observe), the CPU HLO must NOT.
SCENARIOS = {
    "CHIASWARM_ATTENTION": ("local", "flash", True),
    "CHIASWARM_RING_MIN_TOKENS": ("ringmesh", "16", True),
    "CHIASWARM_RING_FLASH": ("ringmesh", "scan", False),
    "CHIASWARM_FLASH_BLOCK_Q": ("flash", "16", True),
    "CHIASWARM_FLASH_BLOCK_KV": ("flash", "16", True),
    "CHIASWARM_FLASH_VMEM_MB": ("flash", "64", False),
    "CHIASWARM_XLA_OPTIONS": (
        "none", "xla_tpu_scoped_vmem_limit_kib=65536", False),
}


def _spawn_probe(program: str, overrides: dict[str, str]) -> dict:
    """One scrubbed-env probe subprocess; raises on failure."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("CHIASWARM_")}
    env.update(overrides)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--probe", program],
        env=env, capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(
            f"probe {program!r} overrides={overrides} failed "
            f"(rc={proc.returncode}):\n{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main() -> int:
    parser = argparse.ArgumentParser(
        description="audit trace-knob -> executable-cache-key "
                    "sensitivity over the real tiny attention programs")
    parser.add_argument("--probe", default=None,
                        help=argparse.SUPPRESS)  # internal subprocess mode
    parser.add_argument("--knobs", default=",".join(SCENARIOS),
                        help="comma-separated subset of: "
                             + ",".join(SCENARIOS))
    parser.add_argument("--json", default=None,
                        help="also write the full report to this path")
    args = parser.parse_args()

    if args.probe is not None:
        return run_probe(args.probe)

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    knobs = [k.strip() for k in args.knobs.split(",") if k.strip()]
    unknown = sorted(set(knobs) - set(SCENARIOS))
    if unknown:
        print(f"key_audit: unknown knob(s) {unknown}; have "
              f"{sorted(SCENARIOS)}", file=sys.stderr)
        return 2

    from chiaswarm_tpu.core.compile_cache import _TRACE_ENV_KNOBS

    report: dict = {"baseline": {}, "scenarios": {}, "violations": []}

    def violation(knob: str, message: str) -> None:
        report["violations"].append({"knob": knob, "message": message})

    uncovered = sorted(set(_TRACE_ENV_KNOBS) - set(SCENARIOS))
    if uncovered:
        violation("<coverage>",
                  f"knob(s) {uncovered} in _TRACE_ENV_KNOBS have no "
                  f"audit scenario — add one before shipping the key")

    try:
        # invariance gate: per program, two scrubbed probes must agree
        # on key AND HLO, and the default key must be the historical
        # 3-tuple (owner, tag, statics) — no knob residue
        programs = sorted({SCENARIOS[k][0] for k in knobs})
        baselines: dict[str, dict] = {}
        for prog in programs:
            first = _spawn_probe(prog, {})
            again = _spawn_probe(prog, {})
            if first["key"] != again["key"]:
                violation("<invariance>",
                          f"{prog}: default key not deterministic")
            if first["hlo_sha"] != again["hlo_sha"]:
                violation("<invariance>",
                          f"{prog}: default build not deterministic")
            key = ast.literal_eval(first["key"])
            if len(key) != 3:
                violation("<invariance>",
                          f"{prog}: default key {first['key']} is not "
                          f"the historical 3-tuple — default-off "
                          f"deployments would lose every warm slot")
            baselines[prog] = first
            report["baseline"][prog] = first

        for knob in knobs:
            prog, value, hlo_changes = SCENARIOS[knob]
            base = baselines[prog]
            probe = _spawn_probe(prog, {knob: value})
            report["scenarios"][knob] = {
                "program": prog, "value": value, "probe": probe}
            key = ast.literal_eval(probe["key"])
            base_key = ast.literal_eval(base["key"])
            if key == base_key:
                violation(knob, f"key is knob-blind: {knob}={value} "
                                f"left the key unchanged ({base['key']})"
                          )
                continue
            if key[:3] != base_key:
                violation(knob, "knob fold rewrote the historical key "
                                "prefix instead of appending — warm "
                                "slots of default deployments would be "
                                "invalidated")
            if (knob, value) not in dict(key[3:]).get("knobs", ()):
                violation(knob, f"key changed but the knob vector does "
                                f"not carry ({knob!r}, {value!r}): "
                                f"{probe['key']}")
            if knob not in probe["fingerprint"]:
                violation(knob, "persistent cache_fingerprint() does "
                                "not carry the knob")
            if prog == "none":
                continue
            if hlo_changes and probe["hlo_sha"] == base["hlo_sha"]:
                violation(knob, f"{prog}: knob changed the key but NOT "
                                f"the built executable — either the "
                                f"scenario shape misses the knob's "
                                f"effect or the knob is host-only and "
                                f"over-keys")
            if not hlo_changes and probe["hlo_sha"] != base["hlo_sha"]:
                violation(knob, f"{prog}: knob documented as TPU-only "
                                f"changed the CPU executable — promote "
                                f"the scenario to hlo_changes=True")
    except Exception as exc:  # noqa: BLE001 — a probe failure IS the report
        print(f"key_audit: {type(exc).__name__}: {exc}", file=sys.stderr)
        return 2

    report["ok"] = not report["violations"]
    if args.json:
        with open(args.json, "w", encoding="utf-8") as fh:
            json.dump(report, fh, indent=2, sort_keys=True)
    print(json.dumps(report, indent=2, sort_keys=True))
    for v in report["violations"]:
        print(f"VIOLATION [{v['knob']}] {v['message']}", file=sys.stderr)
    if report["ok"]:
        print("key_audit: every knob keyed and program-sensitive as "
              "declared", file=sys.stderr)
        return 0
    return 1


if __name__ == "__main__":
    sys.exit(main())
