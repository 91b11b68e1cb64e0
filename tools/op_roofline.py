"""Per-fusion roofline table for the headline SDXL-1024 denoise program.

Thin CLI over ``chiaswarm_tpu.obs.hlocost`` (swarmlens, ISSUE 11) — the
HLO cost model, the profiler join, and the attainment math all live in
the library now, where ``benchmark.py`` stamps them into BENCH json and
``tests/test_op_roofline.py`` costs canned HLO fixtures without a TPU.
This script keeps the operator workflow:

VERDICT r2 item #2's alternative "done" criterion: show, per conv
fusion, how close the compiled program runs to ITS OWN roofline — the
max of its compute time (FLOPs / peak MXU throughput) and its memory
time (HBM bytes / peak bandwidth). A fusion near 100% of that bound has
no headroom left in user code; a fusion far below it marks where XLA's
conv scheduling leaves time on the table.

Method (no TF/tensorboard dependency, no ``--xla_dump_to``):
1. patch the pipelines' ``toplevel_jit`` with the library's AOT-capturing
   :class:`~chiaswarm_tpu.obs.hlocost.ProgramCapture`, so the generate
   program's LoadedExecutable is in hand and its scheduled HLO readable;
2. profile ONE generate call with ``jax.profiler.trace`` and read the
   device plane's per-HLO-op durations (while-loop body ops appear once
   per denoise step, so counts fold the 30 steps in);
3. statically cost each fusion from that HLO;
4. print achieved TFLOP/s, both roofline components, and percent-of-
   roofline per fusion, heaviest first, plus program totals.

Usage (real chip):
    python tools/op_roofline.py [--steps 30] [--size 1024] [--family sdxl]
Peak numbers default to TPU v5e (197 bf16 TFLOP/s, 819 GB/s) and are
overridable via CHIASWARM_PEAK_TFLOPS / CHIASWARM_PEAK_GBPS for other
generations. Results belong in BASELINE.md — and, since ISSUE 11, ride
every BENCH run as the per-config ``roofline`` block.
"""

from __future__ import annotations

import argparse
import glob
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from chiaswarm_tpu.obs.hlocost import (  # noqa: E402
    ProgramCapture,
    attainment_rows,
    collect_op_times,
    compiled_hlo_text,
    conv_attainment_summary,
    default_peaks,
    parse_hlo_text,
)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--family", default=os.environ.get(
        "CHIASWARM_BENCH_FAMILY", "sdxl"))
    parser.add_argument("--size", type=int, default=1024)
    parser.add_argument("--steps", type=int, default=30)
    parser.add_argument("--top", type=int, default=30)
    parser.add_argument("--controlnet", action="store_true",
                        help="profile the combined ControlNet+UNet program "
                             "(BASELINE.json config #4) instead of the base "
                             "generate program")
    parser.add_argument("--img2vid", action="store_true",
                        help="profile the SVD img2vid program (config #5: "
                             "spatio-temporal UNet + temporal-decoder VAE) "
                             "at --size x --size; use --width for the "
                             "published 576x1024 portrait")
    parser.add_argument("--width", type=int, default=None)
    parser.add_argument("--frames", type=int, default=14)
    args = parser.parse_args()

    import jax

    peak_tflops, peak_gbps = default_peaks()

    import chiaswarm_tpu.pipelines.diffusion as diffusion_mod
    from chiaswarm_tpu.core import compat
    from chiaswarm_tpu.pipelines.components import Components
    from chiaswarm_tpu.pipelines.diffusion import (
        DiffusionPipeline,
        GenerateRequest,
    )

    capture = ProgramCapture()
    on_tpu = jax.default_backend() == "tpu"
    size = args.size if on_tpu else 64
    steps = args.steps if on_tpu else 2

    if args.img2vid:
        import numpy as np

        import chiaswarm_tpu.pipelines.video as video_mod
        from chiaswarm_tpu.pipelines.video import (
            Img2VidPipeline,
            VideoComponents,
        )

        with capture.patching(diffusion_mod, video_mod):
            fam = "svd_img2vid" if on_tpu else "tiny_svd"
            vc = VideoComponents.random(fam, seed=0)
            vc.params = jax.device_put(vc.params, jax.devices()[0])
            ipipe = Img2VidPipeline(vc)
            height = size
            width = args.width or size
            frames = args.frames if on_tpu else 4
            cond = np.random.default_rng(0).integers(
                0, 255, (height, width, 3), dtype=np.uint8)
            print(f"compiling img2vid {height}x{width} {frames}f {steps} "
                  f"steps ...", file=sys.stderr)
            ipipe(cond, num_frames=frames, steps=steps, height=height,
                  width=width, seed=0)  # compile + warm
            trace_dir = tempfile.mkdtemp(prefix="xplane_")
            with compat.profiler_trace(trace_dir):
                ipipe(cond, num_frames=frames, steps=steps, height=height,
                      width=width, seed=0)
        _report(trace_dir, capture, args, peak_tflops, peak_gbps)
        return

    family = args.family if on_tpu else "tiny"

    with capture.patching(diffusion_mod):
        c = Components.random(family, seed=0)
        c.params = jax.device_put(c.params, jax.devices()[0])
        pipe = DiffusionPipeline(c)
        controlnet = control_image = None
        if args.controlnet:
            import numpy as np

            from chiaswarm_tpu.pipelines.components import ControlNetBundle

            controlnet = ControlNetBundle.random(family, seed=1)
            controlnet.params = jax.device_put(controlnet.params,
                                               jax.devices()[0])
            control_image = np.random.default_rng(0).integers(
                0, 255, (size, size, 3), dtype=np.uint8)
        req = GenerateRequest(prompt="roofline probe", steps=steps,
                              height=size, width=size, batch=1, seed=0,
                              guidance_scale=7.0, controlnet=controlnet,
                              control_image=control_image)
        print(f"compiling {family}"
              f"{'+controlnet' if args.controlnet else ''} "
              f"{size}px {steps} steps ...", file=sys.stderr)
        pipe(req)  # compile + warm

        trace_dir = tempfile.mkdtemp(prefix="xplane_")
        with compat.profiler_trace(trace_dir):
            pipe(req)
    _report(trace_dir, capture, args, peak_tflops, peak_gbps)


def _report(trace_dir, capture: ProgramCapture, args,
            peak_tflops, peak_gbps) -> None:
    xplane = glob.glob(trace_dir + "/**/*.xplane.pb", recursive=True)
    if not xplane:
        raise FileNotFoundError("profiler produced no xplane.pb")

    times = collect_op_times(xplane[0])
    if not capture.executables:
        raise RuntimeError("no toplevel program captured")
    hlo_text = max(
        (compiled_hlo_text(compiled) for compiled in capture.executables),
        key=len)
    costs = parse_hlo_text(hlo_text)
    rows = attainment_rows(times, costs, peak_tflops=peak_tflops,
                           peak_gbps=peak_gbps)
    summary = conv_attainment_summary(rows)

    print(f"\ndevice op time total (containers excluded): "
          f"{summary['total_ms']:.1f} ms; conv fusions: "
          f"{summary['conv_ms']:.1f} ms "
          f"({summary['conv_share_pct']:.0f}%), "
          f"time-weighted conv roofline attainment: "
          f"{summary['weighted_conv_roof_pct']:.0f}% "
          f"over {summary['sane_ms']:.1f} ms"
          + (f" ({summary['miscosted_fusions']} fusions excluded as "
             f"mis-costed, {summary['miscosted_ms']:.1f} ms)"
             if summary["miscosted_fusions"] else ""))
    print(f"peaks: {peak_tflops:.0f} TFLOP/s, {peak_gbps:.0f} GB/s "
          f"(CHIASWARM_PEAK_TFLOPS/GBPS to override)\n")
    header = (f"{'op':<40} {'kind':>5} {'n':>4} {'ms':>8} {'GFLOP':>9} "
              f"{'MB':>8} {'TFLOP/s':>8} {'bound':>5} {'%roof':>6} "
              f"{'%time':>6}")
    print(header)
    print("-" * len(header))
    for r in rows[: args.top]:
        print(f"{r['name'][:40]:<40} {r['kind']:>5} {r['count']:>4} "
              f"{r['ms']:>8.2f} {r['gflop']:>9.1f} {r['mb']:>8.1f} "
              f"{r['tflops']:>8.1f} {r['bound']:>5} {r['roof_pct']:>6.0f} "
              f"{r['share_pct']:>6.1f}")


if __name__ == "__main__":
    main()
