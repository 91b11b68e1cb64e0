"""Pallas TPU causal flash attention for a chunked prefill: one chunk of
queries at absolute positions ``[q_offset, q_offset + L)`` against a cache
of ``S`` key slots of which only ``[0, q_offset + L)`` are written.

The local flash kernel (ops/flash_attention.py) sweeps every key block of
every call and masks block padding only. This one is its causal twin, a
second entry that shares ``online_softmax_block_update`` with it and
nothing that picks shapes:

- grid = (batch, heads, Q blocks, KV blocks), KV innermost, the running
  max / denominator / accumulator in float32 VMEM scratch, as there.
- ``q_offset`` is a scalar-prefetch operand (traced: one program serves
  every chunk of every prompt up to the cache's capacity). The key
  blocks' index map clamps the block index to the last block that holds
  a key visible to this query block, so a block past it is never
  fetched (the pipeline does not copy a block index it already holds)
  and its grid step is skipped. What lies past ``q_offset + L`` is
  therefore not read at all: it may hold anything.
- inside that bound, a key block wholly above the diagonal of a query
  block is skipped, one on the diagonal is masked (``col <= row``), one
  wholly below it is not masked at all.
- keys may come in two parts: per-head keys ``k`` (B, S, H, D) and a part
  ``k_shared`` (B, S, R) that every head shares (latent attention's
  rotary key), met by a second per-head query part ``q_shared``
  (B, L, H, R). The logits are the sum of the two products, so the
  shared part is never broadcast to the heads in memory. Values may
  differ from keys in head size.
- operands stay (B, tokens, H * D) in memory: a block is a (tokens, D)
  column strip of one head, so nothing is transposed on the way in or
  out. Head sizes are zero-padded to the 128-lane tile where they are
  not a multiple of it (exact: zero lanes add nothing).

Off the chip it runs in Pallas interpret mode, which is how the CPU
tests hold it to the dense masked einsum (tests/test_ling.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chiaswarm_tpu.ops.flash_attention import (
    _LANES,
    _NEG_INF,
    _clamp_block,
    _pad_to,
    online_softmax_block_update,
)

# 1024 x 1024 from a sweep of the kernel alone on a v5e at the text
# cell's sizes (2048 queries x 32 heads against 16,384 slots, eight chunks:
# PERF.md, PR 30): 26.6 ms a job; 2048-row or 2048-key blocks the same
# within 3%, 512-key blocks 75% slower, 512-row blocks 14% slower.
_BLOCK_Q = 1024
_BLOCK_KV = 1024
_VMEM_MB = 48  # the kernel-scoped cap it was swept under (a guard only)


def key_block(q_len: int, capacity: int) -> int:
    """Key rows a block holds for chunks of ``q_len`` queries against
    ``capacity`` slots: never more than the chunk. The written length
    grows by a chunk a call, so a block that is read holds no slot past
    it while the chunks are whole."""
    return min(_clamp_block(q_len, _BLOCK_KV), capacity)


def _causal_kernel(offset_ref, *refs, scale: float, block_q: int,
                   block_kv: int, has_shared: bool):
    if has_shared:
        q_ref, qs_ref, k_ref, ks_ref, v_ref, o_ref, m_scr, l_scr, acc_scr \
            = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    i, j = pl.program_id(2), pl.program_id(3)
    first_row = offset_ref[0] + i * block_q
    last_row = first_row + block_q - 1
    first_col = j * block_kv
    last_col = first_col + block_kv - 1

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    def update(masked: bool):
        m_next, l_next, acc_next = online_softmax_block_update(
            q_ref[0], k_ref[0], v_ref[0],
            m_scr[:, :1], l_scr[:, :1], acc_scr[:],
            scale=scale, kv_len=None, col_offset=first_col,
            row_offset=first_row if masked else None,
            shared=(qs_ref[0], ks_ref[0]) if has_shared else None,
        )
        acc_scr[:] = acc_next
        m_scr[:] = jnp.broadcast_to(m_next, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_next, l_scr.shape)

    # wholly below the diagonal: every key of the block is visible
    pl.when(last_col <= first_row)(functools.partial(update, False))
    # on the diagonal; a block wholly above it (first_col > last_row)
    # is skipped, and the index map has not fetched it either
    pl.when((last_col > first_row) & (first_col <= last_row))(
        functools.partial(update, True))

    @pl.when(j == pl.num_programs(3) - 1)
    def _finalize():
        o_ref[0] = (acc_scr[:] / l_scr[:, :1]).astype(o_ref.dtype)


def _fold(x: jnp.ndarray) -> jnp.ndarray:
    """(B, N, H, D) -> (B, N, H * Dp), D zero-padded to the lane tile."""
    x = _pad_to(x, 3, _LANES)
    return x.reshape(x.shape[0], x.shape[1], -1)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_q", "block_kv", "interpret"),
)
def causal_flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_offset,
    shared_key: tuple[jnp.ndarray, jnp.ndarray] | None = None,
    *,
    scale: float | None = None,
    block_q: int | None = None,
    block_kv: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Causal attention of q (B, L, H, D) at positions ``q_offset + l``
    over k (B, S, H, D) / v (B, S, H, Dv): query ``l`` sees keys ``s <=
    q_offset + l``; ``q_offset + L <= S``. ``shared_key`` = (q_shared
    (B, L, H, R), k_shared (B, S, R)) adds ``q_shared . k_shared`` to
    every head's logits. ``scale`` defaults to ``(D + R) ** -0.5``."""
    b, l, h, d = q.shape
    s, dv = k.shape[1], v.shape[3]
    has_shared = shared_key is not None
    if scale is None:
        scale = float(d + (shared_key[0].shape[3] if has_shared else 0)) \
            ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q = _clamp_block(l, _BLOCK_Q if block_q is None else block_q)
    block_kv = key_block(l, s) if block_kv is None \
        else _clamp_block(s, block_kv)

    def q_index(bi, hi, i, j, offset):
        return (bi, i, hi)

    def last_block(i, offset):
        """The last key block with a key visible to query block ``i``."""
        return (offset[0] + (i + 1) * block_q - 1) // block_kv

    def kv_index(bi, hi, i, j, offset):
        return (bi, jnp.minimum(j, last_block(i, offset)), hi)

    def shared_index(bi, hi, i, j, offset):
        return (bi, jnp.minimum(j, last_block(i, offset)), 0)

    def per_head(x, rows, index):
        """(operand, its block: a column strip of one head, index map)."""
        return x, (1, rows, x.shape[2] // h), index

    operands = [per_head(_fold(q), block_q, q_index),
                per_head(_fold(k), block_kv, kv_index),
                per_head(_fold(v), block_kv, kv_index)]
    if has_shared:
        k_shared = _pad_to(shared_key[1], 2, _LANES)
        operands.insert(1, per_head(_fold(shared_key[0]), block_q, q_index))
        operands.insert(3, (k_shared, (1, block_kv, k_shared.shape[2]),
                            shared_index))
    operands = [(_pad_to(x, 1, block[1]), block, index)
                for x, block, index in operands]
    lp, sp = operands[0][0].shape[1], operands[-1][0].shape[1]
    dvp = operands[-1][1][2]

    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_VMEM_MB << 20,
        )
    of = pl.pallas_call(
        functools.partial(_causal_kernel, scale=scale, block_q=block_q,
                          block_kv=block_kv, has_shared=has_shared),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h, lp // block_q, sp // block_kv),
            in_specs=[pl.BlockSpec(block, index)
                      for _, block, index in operands],
            out_specs=pl.BlockSpec((1, block_q, dvp), q_index),
            scratch_shapes=[
                pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max
                pltpu.VMEM((block_q, _LANES), jnp.float32),  # running denom
                pltpu.VMEM((block_q, dvp), jnp.float32),     # accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, lp, h * dvp), q.dtype),
        interpret=interpret,
        **params,
    )(jnp.asarray(q_offset, jnp.int32).reshape(1),
      *(x for x, _, _ in operands))
    return of[:, :l].reshape(b, l, h, dvp)[..., :dv]
