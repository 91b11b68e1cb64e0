"""Pallas TPU blockwise flash attention (forward, inference).

This is the framework's native-kernel replacement for the reference's
xformers memory-efficient attention (enabled at
swarm/diffusion/diffusion_func.py:86-87). The reference delegates to a
prebuilt CUDA wheel; here the kernel is written for the TPU memory
hierarchy directly:

- grid = (batch*heads, Q blocks, KV blocks), KV innermost ("arbitrary"
  semantics) so the running-softmax accumulator lives in VMEM scratch
  across the KV sweep while Q/KV blocks stream HBM -> VMEM.
- logits/softmax accumulate in float32 on the MXU (`preferred_element_type`)
  regardless of the bf16 input dtype; the output is cast back at the end.
- O(L) memory: no (L, S) attention matrix ever materializes in HBM. That is
  what lets SDXL 1024px self-attention (4096 tokens) and video/long-context
  shapes run without the reference's attention-slicing fallbacks
  (swarm/diffusion/diffusion_func.py:85-88).

Head dims of SD UNets (40/80/160/64/128) are zero-padded up to the 128-lane
tile; padded lanes contribute zero logits and zero values, so results are
exact. Sequence lengths pad up to the block size with -inf-masked logits.

Default blocks (2048 q x 1024 kv) come from an end-to-end sweep on an
installation that is gone; on this chip they read 31.7% / 22.6% of the
roofline at the stated head sizes (`flash_roofline.lat`, ledger, PR 29)
and no other block size has been run. Large q blocks amortize the scratch
traffic; the kernel clamps blocks to the (padded) sequence length.

The same kernel runs in Pallas interpret mode on CPU, which is how the
hermetic test suite validates it against the einsum reference
(tests/test_ops.py) without a TPU.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30  # finite stand-in: true -inf breaks exp() on fully-masked rows

# block-sweep knobs (read once at import): defaults are the tuned v5e
# values. CHIASWARM_FLASH_VMEM_MB sets the kernel-scoped VMEM cap — the
# default 24 MB gives the tuned 2048x1024 blocks headroom over XLA's
# ~16 MB default cap (the SVD video program's surrounding pads push the
# same blocks to 16.4 MB scoped); the cap is a compile-time guard, not an
# allocation, so programs already under 16 MB compile identically. Raise
# further for sweeps of bigger blocks (2048x2048, 4096x1024) on other
# TPU generations; 0 = XLA's default cap.
# an env-pinned block is an EXPLICIT sweep request: EITHER knob disables
# the divisibility auto-pick on BOTH axes, so a datapoint labeled
# "4096x1024" measures exactly 4096x1024 (pinning one axis must not let
# the other silently auto-pick)
_ENV_BLOCK_Q = os.environ.get("CHIASWARM_FLASH_BLOCK_Q")
_ENV_BLOCK_KV = os.environ.get("CHIASWARM_FLASH_BLOCK_KV")
_ENV_PINNED = bool(_ENV_BLOCK_Q or _ENV_BLOCK_KV)
_DEFAULT_BLOCK_Q = int(_ENV_BLOCK_Q) if _ENV_BLOCK_Q else 2048
_DEFAULT_BLOCK_KV = int(_ENV_BLOCK_KV) if _ENV_BLOCK_KV else 1024
_VMEM_MB = int(os.environ.get("CHIASWARM_FLASH_VMEM_MB", "24"))
_LANES = 128


def online_softmax_block_update(q, k, v, m_prev, l_prev, acc_prev, *,
                                scale: float, kv_len, col_offset,
                                row_offset=None, shared=None,
                                rows_per_position: int = 1):
    """One KV block of the running-softmax recurrence, shared by the
    local flash kernel below, the fused ring kernel
    (ops/ring_flash_attention.py) and the causal kernel's decode entries
    (ops/causal_flash_attention.py). All operands are plain arrays (the
    callers own the scratch refs): q (bq, d), k/v (bkv, d), m/l (bq, 1)
    running max/denominator, acc (bq, d) fp32 accumulator. ``col_offset``
    is the block's first GLOBAL kv column (masks padding past
    ``kv_len``); it may be a traced scalar in the ring kernel, where the
    hop index is a grid coordinate. Returns (m_next, l_next, acc_next)
    — bit-identical math to the pre-refactor inline version.

    The causal kernel's two additions, both off by default: ``shared`` =
    (q_s (bq, r), k_s (bkv, r)), a second pair whose products join the
    logits (a key part that every head shares), and ``row_offset``, the
    block's first GLOBAL query row: column ``c`` is visible to row ``r``
    when ``c <= r``; it takes the padding mask's place (a causal caller's
    rows end before its keys do). With ``kv_len`` None too, the block is
    not masked at all.

    The shared-latent decode's one, off by default as well:
    ``rows_per_position`` = g, that many consecutive rows sit at one
    position: row ``r`` sees ``c <= r // g``, tested as ``c * g <= r``
    (no vector division). The probabilities stay float32 against values
    cast to float32 for every caller: on a v5e the decode read the same
    numbers in the same time with them cast to bfloat16 instead (PERF.md,
    PR 34: the MXU takes a float32 product at default precision in one
    bfloat16 pass either way).

    The causal PREFILL no longer comes here (PR 36: its update, by
    tiles, is ``ops/causal_flash_attention.py::_update_by_tiles``, and
    the sliding layers' window went with it); the decode's two sweeps
    do."""
    logits = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if shared is not None:
        logits = logits + jax.lax.dot_general(
            shared[0], shared[1],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    logits = logits * scale

    if kv_len is not None or row_offset is not None:
        col = col_offset + jax.lax.broadcasted_iota(jnp.int32,
                                                    logits.shape, 1)
        if row_offset is None:
            # KV positions past the true sequence length (block padding)
            visible = col < kv_len
        else:
            if rows_per_position != 1:
                col = col * rows_per_position
            row = row_offset + jax.lax.broadcasted_iota(
                jnp.int32, logits.shape, 0)
            visible = col <= row
        logits = jnp.where(visible, logits, _NEG_INF)

    m_cur = jnp.max(logits, axis=-1, keepdims=True)
    m_next = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_next)           # rescale of the old partials
    p = jnp.exp(logits - m_next)               # (bq, bkv) fp32
    l_next = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_next = acc_prev * alpha + jax.lax.dot_general(
        p, v.astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return m_next, l_next, acc_next


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, kv_len: int, block_kv: int):
    """One (q-block, kv-block) tile of the running-softmax recurrence."""
    j = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    m_next, l_next, acc_next = online_softmax_block_update(
        q_ref[0], k_ref[0], v_ref[0],
        m_scr[:, :1], l_scr[:, :1], acc_scr[:],
        scale=scale, kv_len=kv_len, col_offset=j * block_kv,
    )
    acc_scr[:] = acc_next
    m_scr[:] = jnp.broadcast_to(m_next, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_next, l_scr.shape)

    @pl.when(j == n_kv - 1)
    def _finalize():
        o_ref[0] = (acc_scr[:] / l_scr[:, :1]).astype(o_ref.dtype)


def _clamp_block(length: int, block: int) -> int:
    """Shrink a block to the 8-padded sequence length (small inputs)."""
    return min(block, max(8, ((length + 7) // 8) * 8))


def _pick_block(length: int, default: int) -> int:
    """Auto block size for one attention axis: minimize the PADDED
    length — masked block padding still runs on the MXU, so a
    non-divisible tuned block wastes real time (the SVD portrait's
    9216-token level padded to 10240 with 2048-blocks; its 2304-token
    level to 4096/3072). The rule minimizes padded length over the
    FIXED candidate list (1536, 1280, 1024, 768) below the tuned
    default — large blocks only, not divisors of it. Two guards keep
    the r2 sweep's findings intact: candidates stop at 768 (the sweep
    measured small blocks ~75% slower than large ones regardless of
    padding — a 256-divisible length must not fall off that cliff),
    and a smaller block is taken only when it saves >=5% of the
    default's padded length. Power-of-two SD/SDXL
    shapes keep the tuned blocks bit-for-bit. Applied ONLY when neither
    the caller nor the CHIASWARM_FLASH_BLOCK_* env knobs pin a block —
    explicit sweep values are honored as requested."""
    length8 = max(8, ((length + 7) // 8) * 8)
    if length8 <= default:
        return length8
    pad_default = -(-length8 // default) * default
    best_key, best = (pad_default, -default), default
    for cand in (1536, 1280, 1024, 768):
        if cand >= default:
            continue
        padded = -(-length8 // cand) * cand
        if pad_default - padded < 0.05 * pad_default:
            continue  # not worth leaving the tuned block
        key = (padded, -cand)
        if key < best_key:
            best_key, best = key, cand
    return best


def _pad_to(x: jnp.ndarray, axis: int, multiple: int) -> jnp.ndarray:
    size = x.shape[axis]
    target = ((size + multiple - 1) // multiple) * multiple
    if target == size:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - size)
    return jnp.pad(x, pad)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_q", "block_kv", "interpret"),
)
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    scale: float | None = None,
    block_q: int | None = None,
    block_kv: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Blockwise attention over (B, L, H, D) q and (B, S, H, D) k/v."""
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    b, l, h, d = q.shape
    s = k.shape[1]
    out_dtype = q.dtype

    # (B, L, H, D) -> (B*H, L, D): heads become grid-parallel programs
    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

    qf, kf, vf = fold(q), fold(k), fold(v)

    # None = auto (divisibility-aware pick, unless an env sweep pins the
    # block); an explicit caller/env value is honored, clamped only to
    # the padded sequence length. (A per-shape measured-pair override
    # was tried and rejected on an installation that is gone: the
    # kernel alone, tools/flash_sweep.py, preferred 1152x2304 at 2304
    # tokens where the end-to-end program did not. The lesson stands,
    # the numbers do not: judge a block size by the whole program, in
    # a benchmark cell.)
    if block_q is None:
        block_q = (_clamp_block(l, _DEFAULT_BLOCK_Q) if _ENV_PINNED
                   else _pick_block(l, _DEFAULT_BLOCK_Q))
    else:
        block_q = _clamp_block(l, block_q)
    if block_kv is None:
        block_kv = (_clamp_block(s, _DEFAULT_BLOCK_KV) if _ENV_PINNED
                    else _pick_block(s, _DEFAULT_BLOCK_KV))
    else:
        block_kv = _clamp_block(s, block_kv)
    qf = _pad_to(qf, 1, block_q)
    kf = _pad_to(kf, 1, block_kv)
    vf = _pad_to(vf, 1, block_kv)
    qf = _pad_to(qf, 2, _LANES)
    kf = _pad_to(kf, 2, _LANES)
    vf = _pad_to(vf, 2, _LANES)
    dp = qf.shape[2]
    lp, sp = qf.shape[1], kf.shape[1]
    grid = (b * h, lp // block_q, sp // block_kv)

    kernel = functools.partial(
        _flash_kernel, scale=scale, kv_len=s, block_kv=block_kv,
    )
    scratch = [
        pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max
        pltpu.VMEM((block_q, _LANES), jnp.float32),  # running denom
        pltpu.VMEM((block_q, dp), jnp.float32),      # output accumulator
    ]
    params = {}
    if not interpret:
        extra = {"vmem_limit_bytes": _VMEM_MB << 20} if _VMEM_MB else {}
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            **extra,
        )

    of = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dp), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_kv, dp), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, block_kv, dp), lambda bh, i, j: (bh, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dp), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, lp, dp), out_dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        **params,
    )(qf, kf, vf)

    # unfold: (B*H, Lp, Dp) -> (B, L, H, D)
    of = of[:, :l, :d].reshape(b, h, l, d).transpose(0, 2, 1, 3)
    return of
