"""Fused Pallas ring-flash attention: DMA/compute overlap on the ICI ring.

`parallel/ring_attention.py` alternates phases — each hop runs the local
partial softmax, THEN `lax.ppermute` rotates the KV shard — so the MXU
idles during every rotation and the ICI idles during every compute.
Neither ring has run on this chip (ROADMAP R1, D7): what the overlap is
worth is not measured. This kernel issues the NEXT hop's KV transfer
as an async remote DMA (`pltpu.make_async_remote_copy`) into a
double-buffered VMEM slot while the blockwise flash inner loop — the
online-softmax recurrence shared with `ops/flash_attention.py` via
``online_softmax_block_update`` — consumes the CURRENT slot. One
`pl.pallas_call` per shard covers all n hops; no XLA collective ever
lowers for the rotation (the HLO census in tools/contracts/tiny.json
pins that).

Two drive modes, one recurrence:

- fused (TPU)     grid = (B*H, hops), hops innermost/"arbitrary"; the
                  running (m, l, acc) state lives in VMEM scratch across
                  the hop sweep exactly like the local flash kernel's KV
                  sweep. Per hop: start the RDMA of the current KV slot
                  to the right neighbor's next slot, run the flash block
                  update on the current slot, then wait both DMA
                  semaphores and flip slots. A capacity semaphore from
                  the receiver guards the slot against overwrite-while-
                  reading skew; `pltpu.get_barrier_semaphore` aligns the
                  ring before the first send.
- interpret (CPU) `lax.scan` over hops with `lax.ppermute` rotation —
                  the hermetic harness for the SAME in-kernel hop update
                  (`_hop_kernel` runs under Pallas interpret mode with
                  the carried state as inputs/outputs). This is also the
                  software fallback on TPU via CHIASWARM_RING_FLASH=scan.

Call inside `shard_map` with q/k/v sharded on the sequence axis, layout
(B, L, H, D) per shard — the same contract as
`parallel.ring_attention.ring_attention`, which remains the exactness
oracle (tests/test_ring_flash.py pins parity on seq=4/seq=8 and the
data x seq divergence-family trigger mesh).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chiaswarm_tpu.core.compat import axis_size
from chiaswarm_tpu.obs import numerics as _numerics
from chiaswarm_tpu.ops.flash_attention import (
    _LANES,
    _NEG_INF,
    _pad_to,
    online_softmax_block_update,
)


# ---------------------------------------------------------------------------
# the per-hop kernel: the local flash KV sweep with CARRIED state
#
# Identical blockwise recurrence to ops/flash_attention.py::_flash_kernel,
# except the (m, l, acc) accumulator state enters through input refs and
# leaves through output refs instead of being -inf/zero initialized — the
# ring carries it across hops. m/l ride (bq, LANES) lane-broadcast tiles,
# the same scratch layout the local kernel uses.


def _hop_kernel(q_ref, k_ref, v_ref, m_in_ref, l_in_ref, acc_in_ref,
                m_out_ref, l_out_ref, acc_out_ref,
                m_scr, l_scr, acc_scr, *,
                scale: float, kv_len: int, block_kv: int):
    j = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(j == 0)
    def _load():
        m_scr[:] = m_in_ref[0]
        l_scr[:] = l_in_ref[0]
        acc_scr[:] = acc_in_ref[0]

    m_next, l_next, acc_next = online_softmax_block_update(
        q_ref[0], k_ref[0], v_ref[0],
        m_scr[:, :1], l_scr[:, :1], acc_scr[:],
        scale=scale, kv_len=kv_len, col_offset=j * block_kv,
    )
    acc_scr[:] = acc_next
    m_scr[:] = jnp.broadcast_to(m_next, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_next, l_scr.shape)

    @pl.when(j == n_kv - 1)
    def _store():
        m_out_ref[0] = m_scr[:]
        l_out_ref[0] = l_scr[:]
        acc_out_ref[0] = acc_scr[:]


def _hop_call(qf, kf, vf, m, l, acc, *, scale: float, kv_len: int,
              block_q: int, block_kv: int, interpret: bool):
    """One ring hop: run the flash inner loop of the local q shard over
    one KV shard, threading the running state. Shapes are the folded
    (B*H, Lp, Dp) / (B*H, Sp, Dp) layout; m/l are (B*H, Lp, LANES)."""
    bh, lp, dp = qf.shape
    sp = kf.shape[1]
    grid = (bh, lp // block_q, sp // block_kv)
    kernel = functools.partial(
        _hop_kernel, scale=scale, kv_len=kv_len, block_kv=block_kv)

    q_spec = pl.BlockSpec((1, block_q, dp), lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec((1, block_kv, dp), lambda b, i, j: (b, j, 0))
    row_spec = pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0))
    acc_spec = pl.BlockSpec((1, block_q, dp), lambda b, i, j: (b, i, 0))

    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, row_spec, row_spec, acc_spec],
        out_specs=(row_spec, row_spec, acc_spec),
        out_shape=(
            jax.ShapeDtypeStruct((bh, lp, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((bh, lp, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((bh, lp, dp), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, dp), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, m, l, acc)


# ---------------------------------------------------------------------------
# fused TPU kernel: all hops in one pallas_call, RDMA under the compute


def _fused_kernel(nbr_ref,  # scalar prefetch: right neighbor mesh coords
                  q_ref, k_ref, v_ref, o_ref,
                  k_buf, v_buf, m_scr, l_scr, acc_scr,
                  send_sem, recv_sem, free_sem, *,
                  scale: float, kv_len: int, n_shards: int,
                  n_mesh_axes: int):
    bh = pl.program_id(0)
    hop = pl.program_id(1)
    cur = jax.lax.rem(hop, 2)
    nxt = jax.lax.rem(hop + 1, 2)
    right = tuple(nbr_ref[0, a] for a in range(n_mesh_axes))
    left = tuple(nbr_ref[1, a] for a in range(n_mesh_axes))

    @pl.when(jnp.logical_and(bh == 0, hop == 0))
    def _ring_barrier():
        # nobody may RDMA into a neighbor that has not entered the kernel
        barrier = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(
            barrier, inc=1, device_id=left,
            device_id_type=pltpu.DeviceIdType.MESH)
        pltpu.semaphore_signal(
            barrier, inc=1, device_id=right,
            device_id_type=pltpu.DeviceIdType.MESH)
        pltpu.semaphore_wait(barrier, 2)

    @pl.when(hop == 0)
    def _seed():
        # local KV shard into slot 0; grant the upstream sender slot 1
        # (its hop-0 send target). Subsequent grants are issued as each
        # slot's compute retires below.
        k_buf[0] = k_ref[0]
        v_buf[0] = v_ref[0]
        if n_shards > 1:
            pltpu.semaphore_signal(
                free_sem, inc=1, device_id=left,
                device_id_type=pltpu.DeviceIdType.MESH)

    @pl.when(jnp.logical_and(hop < n_shards - 1, n_shards > 1))
    def _send_next():
        # capacity handshake: wait for the receiver's grant on slot nxt,
        # then stream both KV halves of the current slot rightward while
        # the MXU works on the same slot below.
        pltpu.semaphore_wait(free_sem, 1)
        for buf, sems in ((k_buf, 0), (v_buf, 1)):
            pltpu.make_async_remote_copy(
                buf.at[cur], buf.at[nxt],
                send_sem.at[sems], recv_sem.at[sems],
                device_id=right,
                device_id_type=pltpu.DeviceIdType.MESH,
            ).start()

    # ---- the blockwise flash inner loop on the CURRENT slot -------------
    m_prev = jnp.where(hop == 0, jnp.full_like(m_scr[:, :1], _NEG_INF),
                       m_scr[:, :1])
    l_prev = jnp.where(hop == 0, jnp.zeros_like(l_scr[:, :1]), l_scr[:, :1])
    acc_prev = jnp.where(hop == 0, jnp.zeros_like(acc_scr[:]), acc_scr[:])
    m_next, l_next, acc_next = online_softmax_block_update(
        q_ref[0], k_buf[cur], v_buf[cur],
        m_prev, l_prev, acc_prev,
        scale=scale, kv_len=kv_len, col_offset=0,
    )
    acc_scr[:] = acc_next
    m_scr[:] = jnp.broadcast_to(m_next, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_next, l_scr.shape)

    @pl.when(jnp.logical_and(hop < n_shards - 1, n_shards > 1))
    def _drain():
        # our outbound write landed AND the inbound next slot is full
        for sems in (0, 1):
            pltpu.make_async_remote_copy(
                k_buf.at[cur], k_buf.at[nxt],
                send_sem.at[sems], recv_sem.at[sems],
                device_id=right,
                device_id_type=pltpu.DeviceIdType.MESH,
            ).wait()
        # slot `cur` is consumed: grant it to the upstream sender, whose
        # hop+1 send targets it — EXCEPT on the last two hops, where no
        # further send exists (the grant ledger must balance per sweep:
        # n-1 waits == 1 seed grant + n-2 retire grants).

    @pl.when(jnp.logical_and(hop < n_shards - 2, n_shards > 2))
    def _retire_grant():
        pltpu.semaphore_signal(
            free_sem, inc=1, device_id=left,
            device_id_type=pltpu.DeviceIdType.MESH)

    @pl.when(hop == n_shards - 1)
    def _finalize():
        o_ref[0] = (acc_scr[:] / l_scr[:, :1]).astype(o_ref.dtype)


def _ring_flash_fused(q, k, v, *, axis_name: str, scale: float,
                      mesh_axis_names: tuple[str, ...]):
    """TPU path: one pallas_call per shard, hops innermost, KV slots
    double-buffered in VMEM with the RDMA issued under the compute."""
    n = axis_size(axis_name)
    b, l, h, d = q.shape
    s = k.shape[1]
    out_dtype = q.dtype

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

    qf, kf, vf = fold(q), fold(k), fold(v)
    qf = _pad_to(_pad_to(qf, 1, 8), 2, _LANES)
    kf = _pad_to(_pad_to(kf, 1, 8), 2, _LANES)
    vf = _pad_to(_pad_to(vf, 1, 8), 2, _LANES)
    bh, lp, dp = qf.shape
    sp = kf.shape[1]

    # right/left neighbor mesh coordinates (rotate ONLY the seq axis);
    # scalar-prefetched so the kernel can address the RDMA without
    # recomputing axis indices per grid step
    seq_pos = mesh_axis_names.index(axis_name)
    me = [jax.lax.axis_index(a) for a in mesh_axis_names]
    right = list(me)
    right[seq_pos] = jax.lax.rem(me[seq_pos] + 1, n)
    left = list(me)
    left[seq_pos] = jax.lax.rem(me[seq_pos] + n - 1, n)
    nbr = jnp.stack([jnp.stack(right), jnp.stack(left)]).astype(jnp.int32)

    kernel = functools.partial(
        _fused_kernel, scale=scale, kv_len=s, n_shards=n,
        n_mesh_axes=len(mesh_axis_names))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, n),
        # index maps receive the grid indices, then the scalar-prefetch ref
        in_specs=[
            pl.BlockSpec((1, lp, dp), lambda b_, hop_, nbr_: (b_, 0, 0)),
            pl.BlockSpec((1, sp, dp), lambda b_, hop_, nbr_: (b_, 0, 0)),
            pl.BlockSpec((1, sp, dp), lambda b_, hop_, nbr_: (b_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, lp, dp),
                               lambda b_, hop_, nbr_: (b_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, sp, dp), jnp.float32),   # K slots
            pltpu.VMEM((2, sp, dp), jnp.float32),   # V slots
            pltpu.VMEM((lp, _LANES), jnp.float32),  # running max
            pltpu.VMEM((lp, _LANES), jnp.float32),  # running denom
            pltpu.VMEM((lp, dp), jnp.float32),      # output accumulator
            pltpu.SemaphoreType.DMA((2,)),          # send (K, V)
            pltpu.SemaphoreType.DMA((2,)),          # recv (K, V)
            pltpu.SemaphoreType.REGULAR,            # slot capacity grants
        ],
    )
    of = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, lp, dp), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            has_side_effects=True,
            collective_id=7,
        ),
    )(nbr, qf.astype(jnp.float32), kf.astype(jnp.float32),
      vf.astype(jnp.float32))
    return of[:, :l, :d].reshape(b, h, l, d).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# interpret/oracle path: ppermute rotation around the SAME hop kernel


def _ring_flash_scan(q, k, v, *, axis_name: str, scale: float,
                     block_q: int | None, block_kv: int | None,
                     interpret: bool):
    n = axis_size(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    b, l, h, d = q.shape
    s = k.shape[1]

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

    qf, kf, vf = fold(q), fold(k), fold(v)
    if block_q is None:
        block_q = max(8, ((l + 7) // 8) * 8)
    if block_kv is None:
        block_kv = max(8, ((s + 7) // 8) * 8)
    qf = _pad_to(_pad_to(qf, 1, block_q), 2, _LANES)
    kf = _pad_to(_pad_to(kf, 1, block_kv), 2, _LANES)
    vf = _pad_to(_pad_to(vf, 1, block_kv), 2, _LANES)
    bh, lp, dp = qf.shape

    # zero-init carries derive from q arithmetic so they inherit the full
    # varying-axes set under multi-axis shard_map (same stance as
    # parallel/ring_attention.py); XLA folds the zero-multiplies away.
    zrow = jnp.broadcast_to(
        (qf * 0).astype(jnp.float32).sum(axis=-1, keepdims=True),
        (bh, lp, _LANES))
    m0 = zrow + _NEG_INF
    l0 = zrow
    acc0 = (qf * 0).astype(jnp.float32)

    tap_on = _numerics.enabled_for("ring_flash")

    def body(carry, hop):
        k_blk, v_blk, m, lsum, acc = carry
        m, lsum, acc = _hop_call(
            qf, k_blk, v_blk, m, lsum, acc, scale=scale, kv_len=s,
            block_q=block_q, block_kv=block_kv, interpret=interpret)
        if tap_on:
            shard = jax.lax.axis_index(axis_name)
            m = _numerics.tap("ring_flash.hop_rowmax", m,
                              step=hop, shard=shard)
            lsum = _numerics.tap("ring_flash.hop_rowsum", lsum,
                                 step=hop, shard=shard)
            acc = _numerics.tap("ring_flash.hop_acc", acc,
                                step=hop, shard=shard)
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_blk, v_blk, m, lsum, acc), None

    (_, _, m, lsum, acc), _ = jax.lax.scan(
        body, (kf, vf, m0, l0, acc0),
        jnp.arange(n) if tap_on else None,
        length=None if tap_on else n,
    )
    out = acc / lsum[:, :, :1]
    out = out[:, :l, :d].reshape(b, h, l, d).transpose(0, 2, 1, 3)
    if tap_on:
        out = _numerics.tap("ring_flash.out", out,
                            shard=jax.lax.axis_index(axis_name))
    return out.astype(q.dtype)


def _mode() -> str:
    """CHIASWARM_RING_FLASH: fused (TPU default) | scan (software
    fallback / the interpret oracle, CPU default)."""
    return os.environ.get("CHIASWARM_RING_FLASH", "").strip().lower()


def ring_flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: str,
    scale: float | None = None,
    mesh_axis_names: tuple[str, ...] | None = None,
    block_q: int | None = None,
    block_kv: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Full (non-causal) ring-flash attention inside ``shard_map``.

    Per-shard layout (B, L/n, H, D), the `ring_attention` contract. On
    TPU the fused single-kernel path runs (RDMA under compute); anywhere
    else — or under CHIASWARM_RING_FLASH=scan — the ppermute scan drives
    the same hop kernel in Pallas interpret mode, which is how the
    hermetic suite pins parity against the ppermute ring oracle."""
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    fused = (not interpret and _mode() != "scan"
             and mesh_axis_names is not None)
    if fused:
        return _ring_flash_fused(
            q, k, v, axis_name=axis_name, scale=scale,
            mesh_axis_names=mesh_axis_names)
    return _ring_flash_scan(
        q, k, v, axis_name=axis_name, scale=scale,
        block_q=block_q, block_kv=block_kv, interpret=interpret)
