"""Pallas TPU causal flash attention over a partly written cache, in four
entries that share one kernel body, one set of index maps and one
``pallas_call``:

- ``causal_flash_attention``, a chunked prefill: one chunk of queries at
  absolute positions ``[q_offset, q_offset + L)`` against a cache of ``S``
  key slots of which only ``[0, q_offset + L)`` are written; with fewer
  key-value heads than query heads (grouped-query attention) the G
  heads of a key-value head go through as G rows of one position; handed
  latent attention's latents and W_ukv in place of keys and values, it
  up-projects a head's keys and values of a key block at the step that
  reads them (below);
- ``window_flash_attention``, the same under a sliding window: a query
  sees the ``window`` keys up to its own, and a key block wholly outside
  the window of every query of a block is neither fetched nor stepped;
- ``shared_latent_attention``, latent attention's decode in the absorbed
  form: every row's heads as query rows, ALL at the one position behind
  the prompt, against the prompt's latents as one key/value head;
- ``shared_prompt_attention``, grouped-query attention's decode over a
  prompt the rows share: a key-value head's rows x G query rows at one
  position against its separate keys and values, with the log-sum-exp.

The local flash kernel (ops/flash_attention.py) sweeps every key block of
every call and masks block padding only. This one is its causal twin; its
two decode entries share ``online_softmax_block_update`` with the ring
kernel, its two prefill entries cut block pairs into tiles of their own
(``_update_by_tiles``) and update each tile by the local kernel's body
(``update_tile``), and nothing that picks shapes is shared:

- grid = (batch, heads, Q blocks, KV blocks), KV innermost, the running
  max / denominator / accumulator in float32 VMEM scratch, as there.
- ``q_offset`` is a scalar-prefetch operand (traced: one program serves
  every chunk of every prompt up to the cache's capacity, and every
  prompt length of a decode). The key blocks' index map clamps the block
  index to the last block that holds a key visible to this query block,
  so a block past it is never fetched (the pipeline does not copy a
  block index it already holds). What lies past the last visible key's
  block is therefore not read at all: it may hold anything.
- **the prefill's grid ends where the written cache does** (PR 36): the
  key axis of the two prefill entries is ``ceil((q_offset + L) /
  block_kv)`` steps long, a grid dimension traced with the offset
  (``_written_blocks``), not the capacity's. A step past the last
  visible block fetched nothing but was entered and left, 0.25 us each on
  a v5e: 120 of a head's 256 steps over a 16,384-token job's chunks.
- inside that bound, a key block wholly above the diagonal of a query
  block is skipped, one wholly below it is not masked at all, and one
  the diagonal crosses is, in the prefill at one row a position, **cut
  to the diagonal**: its rows go in tiles of a quarter of the block,
  each against the keys up to its own last row and no further, so that
  10 of a 2048-row chunk's 16 sub-tiles of 512 x 512 are scored and 4
  of them masked (by a constant; ``_update_by_tiles``). Where the
  diagonal does not enter a block pair at a multiple of the smaller
  block (an offset off the grid; a job's chunks make none), with rows
  that share a position and under a window the pair is masked whole by
  the traced positions, as every crossed block was before.
- **the 128-head sweep** (PR 36; a v5e, one 2048-token chunk of 128
  heads at each of a job's eight positions against 16,384 slots, ms a
  layer a job, the kernel's call timed alone): the parent's 1024 x 1024
  blocks 135.2; the grid's bound alone 131.3; with the update by tiles
  120.0; then by (query x key) block: 2048 x 1024 **115.0**, 1024 x 2048
  117.7, 2048 x 2048 120.3, 512 x 2048 122.3, 512 x 1024 129.8, 2048 x
  512 135.2, 1024 x 512 142.4, 512 x 512 157.9. The compiler's own
  schedule of the kernel (its final bundles, dumped off the chip:
  PERF.md, PR 36) says why a step stands where it does: the four MXUs'
  slots are full in nine tenths of a step's bundles (one 16-row push
  every 12 bundles each), so what is left is the MXU's own work, three
  128-deep passes a pair for the 320 lanes that are counted.
- **rows that share a position** (``g``): query row ``r`` sits at
  position ``q_offset + r // g`` and sees key ``c`` when ``c <= q_offset
  + r // g``, tested as ``c * g <= q_offset * g + r`` (no division on the
  vector unit). ``g`` = 1 is the latent prefill; ``g`` = all rows with
  ``q_offset = prompt_len - 1`` is the decode, whose two programs are
  letter for letter what they were before the prefill's schedule changed
  (tests/test_ops.py holds their jaxprs' digests; ``g`` = heads would
  be a multi-query prefill; no caller).
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
- **the latent prefill**: the key operand is the cache's latents
  (a (block_kv, 512) window of the (S, 576) cache as it lies, no head
  axis, under the key blocks' clamp) and the value operand W_ukv as
  stored, a head's (512, 128 + 128) column block, fetched once for its
  run of steps. A step that holds work makes the head's keys and values
  of its key block in one product into VMEM scratch
  (``_up_project``), from which the update reads them as it reads
  fetched blocks: no (S, H x 128) keys or values exist in memory, and a
  key block is up-projected once a chunk, since a chunk is one query
  block. Before, ``latent_prefill`` up-projected every written latent
  again each chunk in a loop over two zero-filled (16,384, 16,384)
  arrays a layer: ~0.13 s of a 1.70-s DeepSeek job on a v5e.
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
    update_tile,
)

# The prefill's blocks. 2048 x 1024 from the sweep at 128 heads on a v5e
# (PERF.md, PR 36; the module's docstring has every pair): a chunk of
# 2,048 tokens at one row a position is ONE query block, so with the
# grid's traced bound no step past the diagonal is entered at all, and
# in the latent form each key block of 1024 latents is up-projected once
# a chunk. The grouped entries at the Laguna cell's shapes, ms a layer a
# job with 2048 / 1024 / 512 query rows a block: the full layers' 28.30 /
# 30.21 / 33.91 (2048-key blocks 31.18), the windowed 7.40 / 8.09 / 9.79.
# PR 30's sweep at 32 heads (26.6 ms a job at 1024 x 1024, 2048-row or
# 2048-key blocks the same within 3%, 512-key blocks 75% slower) was of
# the update that PR 36 replaced.
_BLOCK_Q = 2048
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
                   shared_lanes: int | None = None,
                   window: int | None = None, n_rows: int | None = None,
                   by_tiles: bool = False, up_projected: bool = False):
    refs = list(refs)
    q_ref = refs.pop(0)
    qs_ref = refs.pop(0) if has_shared else None
    k_ref = refs.pop(0)
    ks_ref = refs.pop(0) if has_shared else None
    v_ref = k_ref if values_are_keys else refs.pop(0)
    o_ref = refs.pop(0)
    stats_ref = refs.pop(0) if with_stats else None
    m_scr, l_scr, acc_scr, *up_scr = refs
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

    if up_projected:
        # the latent prefill: the key operand is the latents, the value
        # operand this head's columns of W_ukv; the keys and values that
        # the update reads are made here, once a step that holds work
        # (one row a position and no window: every step the grid enters
        # below the diagonal or on it)
        pl.when(first_col <= last_row)(
            functools.partial(_up_project, k_ref, v_ref, *up_scr))
        k_ref, v_ref = up_scr

    def values(masked: bool):
        v = v_ref[0]
        if masked and g > 1:
            # rows that share a position end inside a key block, and what
            # lies past the last visible key is not this call's to vouch
            # for: 0 x NaN would reach the accumulator (at g = 1 a chunk's
            # keys end where its rows do: ``key_block``)
            col = first_key + jax.lax.broadcasted_iota(
                jnp.int32, (block_kv, 1), 0)
            last = last_row
            if n_rows is not None:
                # a call's last query block is padded with rows past its
                # ``n_rows``, which would "see" slots nobody wrote
                last = jnp.minimum(
                    last, _at_group(offset_ref[0], g) + n_rows - 1)
            v = jnp.where(col * g <= last, v, jnp.zeros_like(v))
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

    if by_tiles:
        # the prefill's schedule: a block is whole when it lies below the
        # diagonal AND inside every row's window, skipped when wholly
        # above the diagonal or wholly below every row's window (the
        # index map has fetched neither), crossed otherwise
        whole, reached = last_col <= first_row, first_col <= last_row
        if window is not None:
            span = _at_group(window, g)
            whole &= first_col + span > last_row
            reached &= last_col + span > first_row
        crossed = reached & jnp.logical_not(whole)
        by_cut = functools.partial(
            _update_by_tiles, q_ref, qs_ref, k_ref, ks_ref, v_ref,
            (m_scr, l_scr, acc_scr), scale=scale, g=g, window=window,
            first_row=first_row, first_key=first_key, values=values)
        pl.when(whole)(functools.partial(by_cut, None))
        # one row a position and no window: chunks and blocks of whole
        # multiples let the diagonal enter a pair at one of few keys,
        # and such a pair is cut to it; at any other offset, as with
        # rows that share a position or a window, it is masked whole
        entries = _diagonal_entries(block_q, block_kv) \
            if g == 1 and window is None else ()
        for cut in entries:
            pl.when(crossed & (first_key - first_row == cut))(
                functools.partial(by_cut, cut))
            crossed &= first_key - first_row != cut
        pl.when(crossed)(functools.partial(by_cut, True))
    else:
        # the decode's: one query block at one position. Wholly below
        # the diagonal: every key of the block is visible
        pl.when(last_col <= first_row)(functools.partial(update, False))
        # on the diagonal; a block wholly above it (first_col > last_row)
        # is skipped, and the index map has not fetched it either
        pl.when((last_col > first_row) & (first_col <= last_row))(
            functools.partial(update, True))

    @pl.when(j == pl.num_programs(3) - 1)
    def _finalize():
        if by_tiles:    # the denominator's lanes are summed here, once
            o_ref[0] = (acc_scr[:] / jnp.sum(l_scr[:], axis=-1, keepdims=True)
                        ).astype(o_ref.dtype)
            return
        o_ref[0] = (acc_scr[:] / l_scr[:, :1]).astype(o_ref.dtype)
        if with_stats:
            stats_ref[0] = m_scr[:] + jnp.log(l_scr[:])


def _diagonal_tile(block_q: int) -> int:
    """Query rows a tile holds where a block pair is cut to the
    diagonal: a quarter of the block (512 of 2048 rows), the block itself
    where a quarter is no whole number of sublane tiles."""
    return block_q // 4 if block_q % 32 == 0 else block_q


def _diagonal_entries(block_q: int, block_kv: int) -> range:
    """``first_key - first_row`` of the block pairs the diagonal crosses
    when chunks, offsets and blocks are whole multiples of the smaller
    block: the only ones a job's chunks make."""
    step = min(block_q, block_kv)
    return range(step - block_kv, block_q, step)


def _up_project(latent_ref, w_ref, k_scr, v_scr):
    """One head's keys and values of one key block: the block's latents
    (block_kv, C) times the head's key and value columns of W_ukv
    (C, D + Dv) in one product, accumulated in float32 and rounded to the
    latents' dtype (what the up-projection in XLA gave), into VMEM."""
    kv = jax.lax.dot_general(latent_ref[0], w_ref[...],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    d = k_scr.shape[2]
    k_scr[0] = kv[:, :d].astype(k_scr.dtype)
    v_scr[0] = kv[:, d:].astype(v_scr.dtype)


def _update_by_tiles(q_ref, qs_ref, k_ref, ks_ref, v_ref, scratch, cut, *,
                     scale: float, g: int, window, first_row, first_key,
                     values):
    """One block pair of the prefill's running softmax. ``cut``: None,
    every pair of it is visible: one update over the whole pair; an
    integer, the diagonal enters it at key ``first_row + cut`` (static):
    the rows go in tiles of ``_diagonal_tile``, each against the keys up
    to its last row's and no further, masked by a constant where the
    diagonal crosses; True, masked by the traced positions (any offset,
    rows that share a position, a window).

    What it does otherwise than ``online_softmax_block_update``, each
    judged on a v5e at the DeepSeek cell's shape (PERF.md, PR 36; ms a
    layer's eight chunks, 119.99 with all four): the two products are ONE
    contraction over the concatenated (128 + 128-padded) keys, so that
    the partial products meet as they leave the MXU and the first is not
    stored (+6.7 apart); then a tile's update is the diffusion kernel's,
    ``ops/flash_attention.py::update_tile`` (bfloat16 probabilities +1.9,
    the scale inside ``exp2`` +3.2, the denominator summed over lanes at
    the end +5.3). Key strips inside a pair are slower (512 keys +7.6,
    256 keys +70: the compiler does not overlap a strip's products with
    the last one's softmax), so a pair's keys go in one piece."""
    block_q, block_kv = q_ref.shape[1], k_ref.shape[1]
    traced = cut is True
    diagonal = cut is not None and not traced
    tile = _diagonal_tile(block_q) if diagonal else block_q
    for top in range(0, block_q, tile):
        rows = slice(top, top + tile)
        # local row r sees local key c when c + cut <= r
        end = min(max(top + tile - cut, 0), block_kv) if diagonal \
            else block_kv
        if end == 0:
            continue                # the tile lies wholly above the diagonal
        q, k = q_ref[0, rows, :], k_ref[0, :end, :]
        if qs_ref is not None:
            q = jnp.concatenate([q, qs_ref[0, rows, :]], axis=1)
            k = jnp.concatenate([k, ks_ref[0, :end, :]], axis=1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        visible = None
        if traced:
            col = _at_group(
                first_key + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1),
                g)
            row = first_row + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            visible = col <= row
            if window is not None:
                visible &= col + _at_group(window, g) > row
        elif diagonal and end - 1 + cut > top:
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            visible = col + (cut - top) <= row
        if visible is not None:
            s = jnp.where(visible, s, _NEG_INF)
        update_tile(s, functools.partial(values, True) if traced and g > 1
                    else lambda: v_ref[0, :end, :], scratch, rows,
                    scale=scale)


def _index_maps(block_q: int, block_kv: int, g: int = 1,
                shared_tile: int = 0, window: int | None = None,
                n_kv: int | None = None):
    """(queries' and output's, per-head keys' and values', the shared key
    part's) block index maps over the grid (batch, head, query block, key
    block) with the scalar-prefetched offset: a key block's index is
    clamped to the last block that holds a key visible to the query
    block, so a block past it is neither fetched nor scored; under a
    ``window`` also to the first block that holds one, so that a block
    wholly below the window of every row is not fetched either. The
    shared part is column block ``shared_tile`` of its operand. ``n_kv``
    (the grouped entries give it) keeps the index inside the operand
    where padded rows sit past the last key."""
    def q_index(bi, hi, i, j, offset):
        return (bi, i, hi)

    def last_block(i, offset):
        """The last key block with a key visible to query block ``i``."""
        last_row = _at_group(offset[0], g) + (i + 1) * block_q - 1
        last = (last_row if g == 1 else last_row // g) // block_kv
        return last if n_kv is None else jnp.minimum(last, n_kv - 1)

    def first_block(i, offset):
        """The first key block with a key inside the window of query
        block ``i``'s first row."""
        first_row = _at_group(offset[0], g) + i * block_q
        first = first_row if g == 1 else first_row // g
        return jnp.maximum(first - window + 1, 0) // block_kv

    def kv_index(bi, hi, i, j, offset):
        j = jnp.minimum(j, last_block(i, offset))
        if window is not None:
            j = jnp.maximum(j, first_block(i, offset))
        return (bi, j, hi)

    def shared_index(bi, hi, i, j, offset):
        return (bi, jnp.minimum(j, last_block(i, offset)), shared_tile)

    return q_index, kv_index, shared_index


def _sweep(q_offset, operands, *, grid, out_width: int, out_dtype,
           interpret: bool, name: str | None = None, scratch=(),
           **kernel_options):
    """The one ``pallas_call`` of every entry. ``operands``: (array,
    block, index map) in the kernel's order, queries first, the shared
    key part or the values last, each padded here to whole blocks of
    tokens; ``scratch``: VMEM beyond the running softmax's;
    ``kernel_options`` go to ``_causal_kernel`` (``block_kv`` where the
    last operand's block is not a key block). Returns the read-out (B,
    Lp, H * out_width) and, ``with_stats``, the log-sum-exp of each row's
    visible logits, broadcast over a lane tile (B, Lp, H * 128)."""
    kernel_options.setdefault("block_kv", operands[-1][1][1])
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
        functools.partial(_causal_kernel, block_q=block_q, **kernel_options),
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
                *scratch,
            ],
        ),
        out_shape=out_shape if with_stats else out_shape[0],
        interpret=interpret,
        **params,
    )(jnp.asarray(q_offset, jnp.int32).reshape(1),
      *(x for x, _, _ in operands))


def _written_blocks(q_offset, positions: int, block_kv: int, n_kv: int):
    """The key axis of a prefill's grid: the blocks that hold a slot
    written by the end of this call, ``ceil((q_offset + positions) /
    block_kv)`` of the capacity's ``n_kv``; traced with the offset, so
    that one program serves every chunk and no step is entered past
    them (a step that only clamps its index cost 0.25 us on a v5e, 120 of
    a head's 256 a DeepSeek job: PERF.md, PR 36)."""
    written = (jnp.asarray(q_offset, jnp.int32) + positions + block_kv - 1) \
        // block_kv
    return jnp.clip(written, 1, n_kv)


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
    every head's logits. ``scale`` defaults to ``(D + R) ** -0.5``.

    Latent attention's prefill hands its latents in place of the keys
    and values: k = the cache (B, S, W), a token's latent in its first C
    columns, and v = W_ukv (C, H, D + Dv), a head's key columns and then
    its value columns. A step up-projects its head's keys and values of
    its key block from the latents, in VMEM (``_up_project``), so that
    neither ever reaches memory."""
    b, l, h, d = q.shape
    latent = k.ndim == 3
    s, dv = k.shape[1], v.shape[2] - d if latent else v.shape[3]
    has_shared = shared_key is not None
    if scale is None:
        scale = float(d + (shared_key[0].shape[3] if has_shared else 0)) \
            ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if not latent and k.shape[2] != h:
        if has_shared:
            raise ValueError("grouped query heads take no shared key part")
        return _grouped_prefill(q, k, v, q_offset, scale=scale,
                                block_q=block_q, block_kv=block_kv,
                                interpret=interpret)
    block_q = _clamp_block(l, _BLOCK_Q if block_q is None else block_q)
    block_kv = key_block(l, s) if block_kv is None \
        else _clamp_block(s, block_kv)
    q_index, kv_index, shared_index = _index_maps(block_q, block_kv)

    def per_head(x, rows, index):
        """(operand, its block: a column strip of one head, index map)."""
        return x, (1, rows, x.shape[2] // h), index

    if latent:
        latents, w_ukv, widths = _latent_operands(k, v, d)
        dvp = widths[1]
        operands = [
            per_head(_fold(q), block_q, q_index),
            # the latents have no head axis: their first column block,
            # the key blocks' clamp (the shared part's map)
            (latents, (1, block_kv, w_ukv.shape[0]), shared_index),
            (w_ukv, (w_ukv.shape[0], w_ukv.shape[1] // h),
             lambda bi, hi, i, j, offset: (0, hi))]
        up = {"up_projected": True, "block_kv": block_kv,
              "scratch": [pltpu.VMEM((1, block_kv, width), k.dtype)
                          for width in widths]}
    else:
        operands = [per_head(_fold(q), block_q, q_index),
                    per_head(_fold(k), block_kv, kv_index),
                    per_head(_fold(v), block_kv, kv_index)]
        dvp, up = operands[-1][1][2], {}
    if has_shared:
        k_shared = _pad_to(shared_key[1], 2, _LANES)
        operands.insert(1, per_head(_fold(shared_key[0]), block_q, q_index))
        operands.insert(3, (k_shared, (1, block_kv, k_shared.shape[2]),
                            shared_index))
    of = _sweep(
        q_offset, operands,
        grid=(b, h, -(-l // block_q),
              _written_blocks(q_offset, l, block_kv, -(-s // block_kv))),
        out_width=dvp, out_dtype=q.dtype, interpret=interpret, scale=scale,
        has_shared=has_shared, by_tiles=True, **up)
    return of[:, :l].reshape(b, l, h, dvp)[..., :dv]


def _latent_operands(cache, w_ukv, d: int):
    """(latents (B, S, Cp), W_ukv (Cp, H * (Dp + Dvp)), (Dp, Dvp)) as the
    latent prefill's sweep reads them: the cache as it lies and the
    weights as stored where the latent fills whole lane tiles and so do
    a head's key and value columns (DeepSeek's and Ling's 512 and 128 +
    128: no copy of either), zero-padded copies otherwise (exact: zero
    lanes and rows add nothing)."""
    c, _, width = w_ukv.shape
    if c % _LANES or d % _LANES or (width - d) % _LANES:
        cache = _pad_to(cache[..., :c], 2, _LANES)
        w_ukv = _pad_to(jnp.concatenate(
            [_pad_to(w_ukv[..., :d], 2, _LANES),
             _pad_to(w_ukv[..., d:], 2, _LANES)], axis=2), 0, _LANES)
    dp = _LANES * -(-d // _LANES)
    return (cache, w_ukv.reshape(w_ukv.shape[0], -1),
            (dp, w_ukv.shape[2] - dp))


# ---- grouped query heads: G of them read one key-value head --------------

# Key rows a block of the windowed sweep holds: a 2048-row query block of
# 8 heads is 256 positions, whose windows of 512 keys span 767, so that
# it steps two or three blocks of the local buffer and not the 1024-key
# blocks' two (2,048 keys); 256-key blocks were slower (11.11 ms a layer a
# job against 8.09 at 1024 query rows: PERF.md, PR 36).
_WINDOW_BLOCK_KV = 512


def window_key_block(keys: int) -> int:
    """Key rows a block of ``window_flash_attention`` holds."""
    return _clamp_block(keys, _WINDOW_BLOCK_KV)


def _prefill_blocks(positions: int, g: int, keys: int,
                    window: int | None) -> tuple[int, int]:
    """(query rows, keys) a block of a prefill entry holds for a call of
    ``positions`` positions, ``g`` rows each, against ``keys`` slots: the
    entries' own pick."""
    return (_clamp_block(positions * g, _BLOCK_Q),
            key_block(positions, keys) if window is None
            else window_key_block(keys))


def block_steps(positions: int, g: int, q_offset: int, keys: int,
                window: int | None = None) -> dict[str, int]:
    """One prefill call's grid steps a head of the grid (a key-value
    head), by what the kernel does in them; the kernel's own tests
    (``_causal_kernel``) and its grid's bound (``_written_blocks``) on
    host integers, for the counters:

    - ``whole``: every pair of the block pair is visible, none masked;
    - ``diagonal``: the sub-tiles scored in the pairs the diagonal (or a
      window's edge) crosses: ``_diagonal_tile`` rows x as many keys
      where the schedule cuts the pair to the diagonal, the pair as one
      where it is masked whole (rows that share a position, a window, an
      offset off the block grid);
    - ``dead``: steps entered and left with nothing to do, above the
      diagonal or below every row's window;
    - ``pairs``: the (query row, key) pairs all of those score, masked
      or not."""
    block_q, block_kv = _prefill_blocks(positions, g, keys, window)
    rows = positions * g
    n_kv = -(-keys // block_kv)
    steps = min(max(-(-(q_offset + positions) // block_kv), 1), n_kv)
    tile = _diagonal_tile(block_q)
    cut_to_diagonal = g == 1 and window is None
    aligned = _diagonal_entries(block_q, block_kv)
    count = {"whole": 0, "diagonal": 0, "dead": 0, "pairs": 0}
    for first_row in range(q_offset * g, q_offset * g + rows, block_q):
        last_row = first_row + block_q - 1
        for first_key in range(0, steps * block_kv, block_kv):
            first_col, last_col = first_key * g, (first_key + block_kv - 1) * g
            below = last_col <= first_row
            reached = first_col <= last_row
            if window is not None:
                below &= first_col + window * g > last_row
                reached &= last_col + window * g > first_row
            if below:
                count["whole"] += 1
                count["pairs"] += block_q * block_kv
            elif not reached:
                count["dead"] += 1
            elif cut_to_diagonal and first_key - first_row in aligned:
                cut = first_key - first_row
                for top in range(0, block_q, tile):
                    end = min(max(top + tile - cut, 0), block_kv)
                    count["diagonal"] += -(-end // tile)
                    count["pairs"] += tile * end
            else:
                count["diagonal"] += 1
                count["pairs"] += block_q * block_kv
    return count


def up_projected_blocks(positions: int, q_offset: int, keys: int) -> int:
    """Key blocks one call of the latent prefill (one row a position, no
    window) up-projects a head: one a step that holds work, whole or
    crossed, each query block's up to the last key block it reaches
    inside the grid's bound (the kernel's test and ``_written_blocks`` on
    host integers, for the counters)."""
    block_q, block_kv = _prefill_blocks(positions, 1, keys, None)
    steps = min(max(-(-(q_offset + positions) // block_kv), 1),
                -(-keys // block_kv))
    return sum(min(steps, (first_row + block_q - 1) // block_kv + 1)
               for first_row in range(q_offset, q_offset + positions,
                                      block_q))


def stepped_pairs(rows: int, g: int, q_offset: int, keys: int,
                  window: int) -> int:
    """(query row, key) pairs of the key blocks ``window_flash_attention``
    steps, masked or not, summed over the query blocks of ``rows`` rows
    (``g`` a position, the first at ``q_offset``) against ``keys``
    slots, for the counters."""
    return block_steps(rows // g, g, q_offset, keys, window)["pairs"]


def _by_key_value_head(x: jnp.ndarray, hk: int) -> jnp.ndarray:
    """(B, L, H, D) -> (B, L * G, Hk * Dp): the G = H / Hk query heads
    of one key-value head as G consecutive rows of one position, D
    zero-padded to the lane tile."""
    x = _pad_to(x, 3, _LANES)
    b, l, h, dp = x.shape
    return jnp.swapaxes(x.reshape(b, l, hk, h // hk, dp), 2, 3).reshape(
        b, l * (h // hk), hk * dp)


def _grouped_prefill(q, k, v, q_offset, *, scale, block_q, block_kv,
                     interpret, window: int | None = None,
                     name: str | None = None):
    """``causal_flash_attention`` for q (B, L, H, D) over k (B, S, Hk, D)
    / v (B, S, Hk, Dv) with H = G x Hk: query head j reads key-value
    head j // G. The G heads of a key-value head go through the sweep as
    G rows of one position (the kernel's ``g``), so its key and value
    blocks are fetched once for the whole group; under ``window`` a
    query sees only the ``window`` keys up to its own."""
    b, l, h, _ = q.shape
    s, hk, dv = k.shape[1], k.shape[2], v.shape[3]
    g = h // hk
    if g * hk != h:
        raise ValueError(f"{h} query heads over {hk} key-value heads")
    rows = l * g
    block_q = _clamp_block(rows, _BLOCK_Q if block_q is None else block_q)
    if block_kv is not None:
        block_kv = _clamp_block(s, block_kv)
    elif window is None:
        block_kv = key_block(l, s)
    else:
        block_kv = window_key_block(s)
    n_kv = -(-s // block_kv)
    q_index, kv_index, _ = _index_maps(block_q, block_kv, g, window=window,
                                       n_kv=n_kv)
    qf, kf, vf = _by_key_value_head(q, hk), _fold(k), _fold(v)
    dvp = vf.shape[2] // hk
    operands = [(qf, (1, block_q, qf.shape[2] // hk), q_index),
                (kf, (1, block_kv, kf.shape[2] // hk), kv_index),
                (vf, (1, block_kv, dvp), kv_index)]
    of = _sweep(q_offset, operands,
                grid=(b, hk, -(-rows // block_q),
                      _written_blocks(q_offset, l, block_kv, n_kv)),
                out_width=dvp, out_dtype=q.dtype, interpret=interpret,
                name=name, scale=scale, has_shared=False, g=g, n_rows=rows,
                window=window, by_tiles=True)
    of = of[:, :rows].reshape(b, l, g, hk, dvp)
    return jnp.swapaxes(of, 2, 3).reshape(b, l, h, dvp)[..., :dv]


@functools.partial(
    jax.jit,
    static_argnames=("window", "scale", "block_q", "block_kv", "interpret"),
)
def window_flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_offset,
    *,
    window: int,
    scale: float | None = None,
    block_q: int | None = None,
    block_kv: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Sliding-window causal attention of q (B, L, H, D) at positions
    ``q_offset + l`` over k (B, S, Hk, D) / v (B, S, Hk, Dv), H a
    multiple of Hk: query ``l`` sees keys ``q_offset + l - window < s <=
    q_offset + l``; ``q_offset + L <= S``. A key block wholly below the
    window of every query of a block is neither fetched nor stepped, the
    block on the window's edge is masked. Its own operation name in a
    device trace. ``scale`` defaults to ``D ** -0.5``."""
    if scale is None:
        scale = float(q.shape[3]) ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _grouped_prefill(q, k, v, q_offset, scale=scale, block_q=block_q,
                            block_kv=block_kv, interpret=interpret,
                            window=int(window),
                            name="window_flash_attention")


# The full layers' decode (``shared_prompt_attention``): a job's rows x the
# G heads of one key-value head are a few hundred query rows (32 x 6), so
# a grid step is bound by its two key and value blocks' copies, 512 KB
# each at 2048 keys; fewer, larger steps.
_PROMPT_BLOCK_KV = 2048


def prompt_key_block(capacity: int) -> int:
    """Key rows a block of ``shared_prompt_attention`` holds against
    ``capacity`` slots."""
    return _clamp_block(capacity, _PROMPT_BLOCK_KV)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_q", "block_kv", "interpret"),
)
def shared_prompt_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    n_keys,
    *,
    scale: float | None = None,
    block_q: int | None = None,
    block_kv: int | None = None,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Rows q (R, H, D) that all sit behind the first ``n_keys`` (traced,
    at least 1) of k (S, Hk, D) / v (S, Hk, Dv), H = G x Hk: grouped-query
    attention's decode over a prompt that the rows share. A key-value
    head's R x G query rows go through the sweep at ONE position, so its
    keys and values are read once a call for all rows. Returns (the
    softmax read-out over those keys (R, H, Dv) in float32, the
    log-sum-exp of each row's scaled logits (R, H) float32), by which a
    caller joins further keys' partial softmax to this one exactly. Key
    blocks past ``n_keys`` are not read. ``scale`` defaults to ``D **
    -0.5``."""
    r, h, d = q.shape
    s, hk, dv = v.shape
    g_heads = h // hk
    if g_heads * hk != h:
        raise ValueError(f"{h} query heads over {hk} key-value heads")
    if scale is None:
        scale = float(d) ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = r * g_heads
    block_q = _clamp_block(n, _DECODE_BLOCK_Q if block_q is None
                           else block_q)
    block_kv = prompt_key_block(s) if block_kv is None \
        else _clamp_block(s, block_kv)
    rows = -(-n // block_q) * block_q
    n_kv = -(-s // block_kv)
    # every row, padding included, at the one position n_keys - 1
    q_index, kv_index, _ = _index_maps(block_q, block_kv, rows, n_kv=n_kv)
    qf = _by_key_value_head(q[None], hk)
    kf, vf = _fold(k[None]), _fold(v[None])
    dvp = vf.shape[2] // hk
    operands = [(qf, (1, block_q, qf.shape[2] // hk), q_index),
                (kf, (1, block_kv, kf.shape[2] // hk), kv_index),
                (vf, (1, block_kv, dvp), kv_index)]
    o, stats = _sweep(
        jnp.asarray(n_keys, jnp.int32) - 1, operands,
        grid=(1, hk, rows // block_q, n_kv), out_width=dvp,
        out_dtype=jnp.float32, interpret=interpret,
        name="shared_prompt_attention", scale=scale, has_shared=False,
        g=rows, with_stats=True)
    o = o[0, :n].reshape(r, g_heads, hk, dvp)
    stats = stats[0, :n].reshape(r, g_heads, hk, _LANES)[..., 0]
    return (jnp.swapaxes(o, 1, 2).reshape(r, h, dvp)[..., :dv],
            jnp.swapaxes(stats, 1, 2).reshape(r, h))


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
