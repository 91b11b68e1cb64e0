"""Per-layer metrics: one data file a metric (``metrics/<name>.json``
names a reader and its arguments), one small module a reader
(``readers/<reader>.py`` with ``read(context, **arguments)``).

A reader takes its number from the run's spans, counters or trace. One
that finds nothing to read returns None, and the metric is left out of
the result line; it never returns 0 for a share.
"""

from __future__ import annotations

import importlib
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent.parent


class NoPeaks(KeyError):
    """The device's kind is not in ``peaks.json``: no share of a peak or
    of a roofline can be named on it."""


def peaks_for(device_kind: str) -> dict:
    """The chip's published peaks; a kind not in the table is an error,
    never a default."""
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise NoPeaks(f"no peaks for device kind {device_kind!r}: add it "
                      f"to perfbench/peaks.json with its source")
    return table[device_kind]


class Context:
    """What one traced run hands its readers."""

    def __init__(self, *, workload, config, mix, ran, good, latencies,
                 window_s, device, capture) -> None:
        self.workload, self.config, self.mix = workload, config, mix
        self.ran, self.good, self.latencies = ran, good, latencies
        self.window_s, self.device, self.capture = window_s, device, capture
        self._form = self._costs = None

    @property
    def kind(self):
        """The module of the configuration's kind (``perfbench/kinds``)."""
        from perfbench import kinds

        return kinds.of(self.config)

    @property
    def peaks(self) -> dict:
        return peaks_for(self.device["kind"])

    def counter_delta(self, family: str, field: str | None = None) -> float:
        """Window delta of a process-global metric family, summed over
        its label sets (``field``: ``sum`` / ``count`` of a histogram)."""
        def total(snapshot):
            values = snapshot.get(family, {}).get("values", {})
            return sum((v[field] if field else v) for v in values.values())

        return total(self.ran["after"]["registry"]) \
            - total(self.ran["before"]["registry"])

    def stepper_delta(self, key: str) -> float:
        return float(self.ran["after"]["stepper"].get(key, 0)) \
            - float(self.ran["before"]["stepper"].get(key, 0))

    @property
    def form(self) -> dict | None:
        """The traced window in the reduction's plain form."""
        traced = self.ran.get("traced")
        if self._form is None and traced:
            from perfbench import trace

            self._form = trace.load(traced["dir"], traced["window_s"])
        return self._form

    @property
    def costs(self) -> dict:
        """Static cost of every operation of every program captured,
        keyed by (operation name, result shape); Mosaic calls at the
        kernel sites the configuration's kind states."""
        if self._costs is None:
            from perfbench import hlo

            executables = self.capture.executables if self.capture else ()
            sites = (self.kind.kernel_sites(self.config)
                     if executables else None)
            self._costs = {}
            for compiled in executables:
                parsed = hlo.parse_hlo_text(
                    hlo.compiled_hlo_text(compiled), sites)
                for name, cost in parsed.items():
                    self._costs[(name, cost["shape"])] = cost
        return self._costs

    def device_times(self) -> dict:
        from perfbench import trace

        form = self.form
        if not form or not form["device"]:
            return {}
        return {"busy_s": trace.busy_seconds(form),
                "window_s": form["window_s"]}

    def breakdown(self) -> dict | None:
        from perfbench import trace

        form = self.form
        if not form or not form["device"]:
            return None
        ops = sorted(trace.op_totals(form).items(),
                     key=lambda kv: -kv[1]["seconds"])[:10]
        return {"device_ops": [[name, t["seconds"]] for name, t in ops],
                "idle_gaps": trace.idle_gaps(form)}


def read(metric: str, context: Context) -> float | None:
    spec = json.loads((HERE / "metrics" / f"{metric}.json").read_text())
    module = importlib.import_module(f"perfbench.readers.{spec['reader']}")
    value = module.read(context, **spec.get("args", {}))
    return None if value is None else float(value)
