"""Micro-benchmark the Pallas flash-attention kernel across block sizes.

Times the kernel alone (no UNet) at a given (B, L, H, D) self-attention
shape on the chip, for a list of (block_q, block_kv) candidates. For
tuning `_pick_block` (ops/flash_attention.py) at non-power-of-two
serving levels — e.g. the SVD portrait's 2304- and 9216-token spatial
levels. A block size that wins here is a candidate, not a result: judge
it by the whole program (ops/flash_attention.py, `flash_attention`).

    python tools/flash_sweep.py --batch 28 --seq 2304 --heads 10 \
        --blocks 768x768,1152x1152,1152x2304,2304x1152

Prints one line per candidate: median ms over --iters and achieved
TFLOP/s (4*B*H*L^2*D flops).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=28)
    ap.add_argument("--seq", type=int, default=2304)
    ap.add_argument("--heads", type=int, default=10)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--iters", type=int, default=7)
    ap.add_argument("--chain", type=int, default=50)
    ap.add_argument("--blocks", type=str,
                    default="768x768,1152x1152,1152x2304,2304x1152")
    args = ap.parse_args()

    from chiaswarm_tpu.ops.flash_attention import flash_attention

    b, l, h, d = args.batch, args.seq, args.heads, args.head_dim
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, l, h, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, l, h, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, l, h, d), jnp.bfloat16)
    flops = 4.0 * b * h * l * l * d

    # measure the dispatch + scalar-fetch roundtrip with an empty "chain"
    # and subtract it from every candidate's wall clock, otherwise it
    # biases per-call time by roundtrip/chain
    base_run = jax.jit(lambda qa: jnp.sum(qa.astype(jnp.float32)))
    float(base_run(q))
    base_times = []
    for _ in range(args.iters):
        t0 = time.perf_counter()
        float(base_run(q))
        base_times.append(time.perf_counter() - t0)
    roundtrip = sorted(base_times)[len(base_times) // 2]
    print(f"roundtrip baseline: {roundtrip * 1e3:.1f} ms (subtracted)")

    for spec in args.blocks.split(","):
        bq, bkv = (int(x) for x in spec.split("x"))
        try:
            # one kernel run is short next to a host roundtrip, so chain
            # --chain dependent kernel calls inside one jit (each
            # iteration's output feeds the next query; no CSE), fetch a
            # scalar once, and subtract the empty-chain roundtrip
            # measured above
            n = args.chain

            def chained(qa, ka, va, bq=bq, bkv=bkv):
                # ka/va must be the jitted function's own parameters —
                # closing over the outer arrays would embed them as
                # program constants
                def body(_, qc):
                    return flash_attention(qc, ka, va,
                                           block_q=bq, block_kv=bkv)

                return jnp.sum(
                    jax.lax.fori_loop(0, n, body, qa).astype(jnp.float32))

            run = jax.jit(chained)
            float(run(q, k, v))
            times = []
            for _ in range(args.iters):
                t0 = time.perf_counter()
                float(run(q, k, v))
                times.append(
                    max(time.perf_counter() - t0 - roundtrip, 0.0) / n)
            ms = sorted(times)[len(times) // 2] * 1e3
            print(f"{bq}x{bkv}: {ms:8.3f} ms  "
                  f"{flops / (ms * 1e-3) / 1e12:6.1f} TFLOP/s")
        except Exception as e:  # noqa: BLE001 - report and keep sweeping
            print(f"{bq}x{bkv}: FAILED {type(e).__name__}: {str(e)[:120]}")


if __name__ == "__main__":
    main()
