"""Pallas TPU causal flash attention over a partly written cache, in two
entries that share one kernel body, one set of index maps and one
``pallas_call``:

- ``causal_flash_attention``, a chunked prefill: one chunk of queries at
  absolute positions ``[q_offset, q_offset + L)`` against a cache of ``S``
  key slots of which only ``[0, q_offset + L)`` are written;
- ``shared_latent_attention``, latent attention's decode in the absorbed
  form: every row's heads as query rows, ALL at the one position behind
  the prompt, against the prompt's latents as one key/value head.

The local flash kernel (ops/flash_attention.py) sweeps every key block of
every call and masks block padding only. This one is its causal twin; it
shares ``online_softmax_block_update`` with it and nothing that picks
shapes:

- grid = (batch, heads, Q blocks, KV blocks), KV innermost, the running
  max / denominator / accumulator in float32 VMEM scratch, as there.
- ``q_offset`` is a scalar-prefetch operand (traced: one program serves
  every chunk of every prompt up to the cache's capacity, and every
  prompt length of a decode). The key blocks' index map clamps the block
  index to the last block that holds a key visible to this query block,
  so a block past it is never fetched (the pipeline does not copy a
  block index it already holds) and its grid step is skipped. What lies
  past the last visible key's block is therefore not read at all: it may
  hold anything.
- inside that bound, a key block wholly above the diagonal of a query
  block is skipped, one on the diagonal is masked, one wholly below it
  is not masked at all.
- **rows that share a position** (``g``): query row ``r`` sits at
  position ``q_offset + r // g`` and sees key ``c`` when ``c <= q_offset
  + r // g``, tested as ``c * g <= q_offset * g + r`` (no division on the
  vector unit). ``g`` = 1 is the prefill, whose program is letter for
  letter what it was before ``g`` came (tests/test_ops.py holds its
  jaxpr's digest); ``g`` = all rows with ``q_offset = prompt_len - 1`` is
  the decode (``g`` = heads would be a multi-query prefill; no caller).
  With ``g`` > 1 the rows of a block end inside a key block, so the
  block that holds the last visible key also holds slots nobody wrote:
  there the values past it are zeroed (a masked key's probability is 0,
  and 0 x NaN would still reach the accumulator).
- keys may come in two parts: per-head keys ``k`` (B, S, H, D) and a part
  ``k_shared`` (B, S, R) that every head shares (latent attention's
  rotary key), met by a second per-head query part ``q_shared``
  (B, L, H, R). The logits are the sum of the two products, so the
  shared part is never broadcast to the heads in memory. Values may
  differ from keys in head size, or BE the keys (the decode: a latent is
  both, one block fetched for the two).
- **a second output** (the decode's): each row's log-sum-exp of its
  visible logits, float32, by which the caller joins the softmax over
  further keys of its own to this one exactly; the read-out then leaves
  in float32 too.
- operands stay (B, tokens, H * D) in memory: a block is a (tokens, D)
  column strip of one head, so nothing is transposed on the way in or
  out. Head sizes are zero-padded to the 128-lane tile where they are
  not a multiple of it (exact: zero lanes add nothing). The decode reads
  both parts out of the (S, 576) cache where they lie: the 512 latent
  columns as four lane tiles, the 64 rotary columns as the fifth, whose
  other 64 lanes lie past the cache's width and are zeroed in the
  kernel; nothing of the cache is copied, sliced or padded a step (the
  slice alone cost 31 us a call, PERF.md, PR 34).

Off the chip it runs in Pallas interpret mode, which is how the CPU
tests hold it to the dense masked einsum (tests/test_ling.py,
tests/test_latent_decode.py).
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


# The decode's blocks (``shared_latent_attention``), from a sweep inside the
# whole decode program of both text cells on a v5e (PERF.md, PR 34): all
# 2048 query rows (16 rows x 128 heads) in one block, so the cache is read
# once a call, and 512 keys a block. The kernel's device time a call, in
# ms, at 16,384 keys, q x kv: 2048 x 512 0.437 (85% of the bf16 peak),
# 1024 x 1024 0.442, 1024 x 512 0.444, 512 x 1024 0.451, 512 x 2048 0.456,
# 2048 x 1024 0.466, 512 x 512 0.479, 1024 x 2048 0.486, 2048 x 256 0.505,
# 1024 x 256 0.544, 2048 x 2048 0.576; at 1024 query rows (32 x 32 heads):
# 1024 x 512 0.221, 512 x 1024 0.225, 1024 x 1024 0.232, 512 x 512 0.239,
# 1024 x 256 0.271. Judged by the kernel's own events: the sampled tokens
# differ between two variants, so the expert loops beside it move by more
# than the variants differ.
_DECODE_BLOCK_Q = 2048
_DECODE_BLOCK_KV = 512


def shared_key_block(capacity: int) -> int:
    """Key rows a block of the decode's sweep holds against ``capacity``
    slots."""
    return _clamp_block(capacity, _DECODE_BLOCK_KV)


def _at_group(x, g: int):
    """A position (or key column) on the scale of query rows, ``g`` of
    which share a position; the value itself at ``g`` = 1, so the
    prefill's program is letter for letter what it was."""
    return x if g == 1 else x * g


def _causal_kernel(offset_ref, *refs, scale: float, block_q: int,
                   block_kv: int, has_shared: bool, g: int = 1,
                   values_are_keys: bool = False, with_stats: bool = False,
                   shared_lanes: int | None = None):
    refs = list(refs)
    q_ref = refs.pop(0)
    qs_ref = refs.pop(0) if has_shared else None
    k_ref = refs.pop(0)
    ks_ref = refs.pop(0) if has_shared else None
    v_ref = k_ref if values_are_keys else refs.pop(0)
    o_ref = refs.pop(0)
    stats_ref = refs.pop(0) if with_stats else None
    m_scr, l_scr, acc_scr = refs
    i, j = pl.program_id(2), pl.program_id(3)
    # rows and columns on one scale: row r sits at position r // g, so
    # column c is visible to it when c * g <= r
    first_row = _at_group(offset_ref[0], g) + i * block_q
    last_row = first_row + block_q - 1
    first_key = j * block_kv
    first_col = _at_group(first_key, g)
    last_col = _at_group(first_key + block_kv - 1, g)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    def values(masked: bool):
        v = v_ref[0]
        if masked and g > 1:
            # rows that share a position end inside a key block, and what
            # lies past the last visible key is not this call's to vouch
            # for: 0 x NaN would reach the accumulator (at g = 1 a chunk's
            # keys end where its rows do: ``key_block``)
            col = first_key + jax.lax.broadcasted_iota(
                jnp.int32, (block_kv, 1), 0)
            v = jnp.where(col * g <= last_row, v, jnp.zeros_like(v))
        return v

    def shared_part():
        q_shared, k_shared = qs_ref[0], ks_ref[0]
        if shared_lanes is not None:
            # the block reaches past the operand's last column: those
            # lanes hold whatever its last tile's padding does
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
            k_shared = jnp.where(lane < shared_lanes, k_shared,
                                 jnp.zeros_like(k_shared))
        return q_shared, k_shared

    def update(masked: bool):
        m_next, l_next, acc_next = online_softmax_block_update(
            q_ref[0], k_ref[0], values(masked),
            m_scr[:, :1], l_scr[:, :1], acc_scr[:],
            scale=scale, kv_len=None, col_offset=first_key,
            row_offset=first_row if masked else None,
            shared=shared_part() if has_shared else None,
            rows_per_position=g,
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
        if with_stats:
            stats_ref[0] = m_scr[:] + jnp.log(l_scr[:])


def _index_maps(block_q: int, block_kv: int, g: int = 1,
                shared_tile: int = 0):
    """(queries' and output's, per-head keys' and values', the shared key
    part's) block index maps over the grid (batch, head, query block, key
    block) with the scalar-prefetched offset: a key block's index is
    clamped to the last block that holds a key visible to the query
    block, so a block past it is neither fetched nor scored. The shared
    part is column block ``shared_tile`` of its operand."""
    def q_index(bi, hi, i, j, offset):
        return (bi, i, hi)

    def last_block(i, offset):
        """The last key block with a key visible to query block ``i``."""
        last_row = _at_group(offset[0], g) + (i + 1) * block_q - 1
        return (last_row if g == 1 else last_row // g) // block_kv

    def kv_index(bi, hi, i, j, offset):
        return (bi, jnp.minimum(j, last_block(i, offset)), hi)

    def shared_index(bi, hi, i, j, offset):
        return (bi, jnp.minimum(j, last_block(i, offset)), shared_tile)

    return q_index, kv_index, shared_index


def _sweep(q_offset, operands, *, grid, out_width: int, out_dtype,
           interpret: bool, name: str | None = None, **kernel_options):
    """The one ``pallas_call`` of both entries. ``operands``: (array,
    block, index map) in the kernel's order, queries first, the shared
    key part or the values last, each padded here to whole blocks of
    tokens; ``kernel_options`` go to ``_causal_kernel``. Returns the
    read-out (B, Lp, H * out_width) and, ``with_stats``, the log-sum-exp
    of each row's visible logits, broadcast over a lane tile
    (B, Lp, H * 128)."""
    operands = [(_pad_to(x, 1, block[1]), block, index)
                for x, block, index in operands]
    b, lp = operands[0][0].shape[:2]
    h, block_q, q_index = grid[1], operands[0][1][1], operands[0][2]
    with_stats = kernel_options.get("with_stats", False)
    out_shape = [jax.ShapeDtypeStruct((b, lp, h * out_width), out_dtype)]
    out_specs = [pl.BlockSpec((1, block_q, out_width), q_index)]
    if with_stats:
        out_shape.append(jax.ShapeDtypeStruct((b, lp, h * _LANES),
                                              jnp.float32))
        out_specs.append(pl.BlockSpec((1, block_q, _LANES), q_index))
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_VMEM_MB << 20,
        )
    if name is not None:
        params["name"] = name
    return pl.pallas_call(
        functools.partial(_causal_kernel, block_q=block_q,
                          block_kv=operands[-1][1][1], **kernel_options),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pl.BlockSpec(block, index)
                      for _, block, index in operands],
            out_specs=out_specs if with_stats else out_specs[0],
            scratch_shapes=[
                pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max
                pltpu.VMEM((block_q, _LANES), jnp.float32),  # running denom
                pltpu.VMEM((block_q, out_width), jnp.float32),  # accumulator
            ],
        ),
        out_shape=out_shape if with_stats else out_shape[0],
        interpret=interpret,
        **params,
    )(jnp.asarray(q_offset, jnp.int32).reshape(1),
      *(x for x, _, _ in operands))


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
    q_index, kv_index, shared_index = _index_maps(block_q, block_kv)

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
    dvp = operands[-1][1][2]
    of = _sweep(
        q_offset, operands,
        grid=(b, h, -(-l // block_q), -(-s // block_kv)), out_width=dvp,
        out_dtype=q.dtype, interpret=interpret, scale=scale,
        has_shared=has_shared)
    return of[:, :l].reshape(b, l, h, dvp)[..., :dv]


@functools.partial(
    jax.jit,
    static_argnames=("value_width", "scale", "block_q", "block_kv",
                     "interpret"),
)
def shared_latent_attention(
    q: jnp.ndarray,
    keys: jnp.ndarray,
    n_keys,
    *,
    value_width: int,
    scale: float | None = None,
    block_q: int | None = None,
    block_kv: int | None = None,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Rows q (N, W) that all sit behind the first ``n_keys`` (traced, at
    least 1) of ``keys`` (S, W), ONE key/value head: a key is its whole
    row, its value the row's first ``value_width`` columns (latent
    attention's absorbed form: the latent is both, the rotary key rides
    beside it). Returns (the softmax read-out over those keys (N,
    value_width) in float32, the log-sum-exp of each row's scaled logits
    (N,) float32), by which a caller joins further keys' partial softmax
    to this one exactly. ``scale`` defaults to ``W ** -0.5``."""
    n, w = q.shape
    s, rank = keys.shape[0], value_width
    if scale is None:
        scale = float(w) ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q = _clamp_block(n, _DECODE_BLOCK_Q if block_q is None
                           else block_q)
    block_kv = shared_key_block(s) if block_kv is None \
        else _clamp_block(s, block_kv)
    rows = -(-n // block_q) * block_q
    q_latent = _pad_to(q[:, :rank], 1, _LANES)
    q_rotary = _pad_to(q[:, rank:], 1, _LANES)
    width, rotary = q_latent.shape[1], q_rotary.shape[1]
    # latents that fill whole lane tiles with a rotary part inside the
    # next one (512 + 64): both blocks are windows of the cache as it
    # lies, no copy and no slice a step, and the kernel zeroes the lanes
    # of the rotary window past the cache's width. Other widths: padded
    # copies of the two parts.
    in_place = rank % _LANES == 0 and w - rank <= _LANES
    if in_place:
        latents = k_rotary = keys
    else:
        latents = _pad_to(keys[:, :rank], 1, _LANES)
        k_rotary = _pad_to(keys[:, rank:], 1, _LANES)
    # every row, padding included, at the one position n_keys - 1
    q_index, kv_index, shared_index = _index_maps(
        block_q, block_kv, rows, rank // _LANES if in_place else 0)
    operands = [(q_latent[None], (1, block_q, width), q_index),
                (q_rotary[None], (1, block_q, rotary), q_index),
                (latents[None], (1, block_kv, width), kv_index),
                (k_rotary[None], (1, block_kv, rotary), shared_index)]
    o, stats = _sweep(
        jnp.asarray(n_keys, jnp.int32) - 1, operands,
        grid=(1, 1, rows // block_q, -(-s // block_kv)), out_width=width,
        out_dtype=jnp.float32, interpret=interpret,
        name="shared_latent_attention", scale=scale, has_shared=True,
        g=rows, values_are_keys=True, with_stats=True,
        shared_lanes=w - rank if in_place else None)
    return o[0, :n, :rank], stats[0, :n, 0]
