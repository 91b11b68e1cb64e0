"""Attention dispatch — the TPU replacement for the reference's xformers
memory-efficient attention (enabled at swarm/diffusion/diffusion_func.py:86-87).

Five implementations behind one function:

- ``"xla"``        — plain einsum softmax attention; XLA fuses it well for
                     the small/medium sequence lengths of image latents.
                     Always correct; the golden reference for kernel tests.
- ``"flash"``      — Pallas blockwise flash-attention kernel
                     (ops/flash_attention.py), O(L) memory, targets the MXU;
                     used on TPU for large token counts (SDXL 1024px
                     self-attention = 4096 tokens, video).
- ``"ring"``       — sequence-parallel ring attention
                     (parallel/ring_attention.py): tokens sharded over the
                     mesh's ``seq`` axis, KV blocks rotated with ppermute.
                     Engaged when the pipeline runs under
                     parallel.context.param_mesh on a seq>1 mesh —
                     self-attention only (cross-attention KV is 77 tokens).
                     The exactness oracle for the fused kernel.
- ``"ring_flash"`` — fused Pallas ring-flash kernel
                     (ops/ring_flash_attention.py): the flash inner loop
                     with the next hop's KV shard streaming in as an async
                     remote DMA under the compute. EXPLICIT only (impl= or
                     CHIASWARM_ATTENTION): the fused grid has not yet run
                     on a chip, and ``auto`` never selects a kernel that
                     has not; on CPU it rides Pallas interpret mode.
- ``"auto"``       — ring when a seq-parallel mesh is active and shapes
                     qualify, else flash on TPU when shapes qualify, else
                     xla. CHIASWARM_ATTENTION=<kind> overrides the pick.

``causal=True`` is a second entry, not a sixth kind: a chunk of a
prefill against a partly written cache goes to the causal flash kernel
(ops/causal_flash_attention.py) whatever the backend, and none of the
calls above can reach it. ``shared_latent_attention`` below is that
kernel's other entry, behind the same door: a decode step's rows, all at
one position, against one shared key/value head, with each row's
log-sum-exp beside the read-out; models/text_layers.py imports no kernel
module for either.

All take (B, L, H, D) query / (B, S, H, D) key-value tensors and return
(B, L, H, D). Head-batched layouts keep the last dim = head_dim (128-lane
friendly) and let the kernel tile L/S onto the MXU.

Low-precision activations (ISSUE 18, the PR-8 weight-path residue): with
CHIASWARM_ACTIVATIONS=int8|fp8 the q/k/v operands pass through
convert.quantize.fake_quant_activation — per-tensor dynamic-absmax
quantize + dequant-at-use inside the traced program — BEFORE the
swarmlens taps, so a bisect of a quantized-vs-fp twin pair localizes the
first attention layer whose inputs lost too much.
"""

from __future__ import annotations

from typing import Literal

import jax
import jax.numpy as jnp

from chiaswarm_tpu.obs import numerics as _numerics

AttentionImpl = Literal["auto", "xla", "flash", "ring", "ring_flash"]

_RING_MIN_TOKENS = 1024  # same bar as the flash kernel; env-overridable

_IMPLS = ("auto", "xla", "flash", "ring", "ring_flash")


def _ring_min_tokens() -> int:
    import os

    return int(os.environ.get("CHIASWARM_RING_MIN_TOKENS", _RING_MIN_TOKENS))


def _env_impl() -> str | None:
    """CHIASWARM_ATTENTION: operator override of the ``auto`` pick (a
    sweep knob — flip kinds without touching worker config).
    Explicit ``impl=`` callers are never overridden."""
    import os

    raw = os.environ.get("CHIASWARM_ATTENTION", "").strip().lower()
    return raw if raw in _IMPLS else None


def _bhd_spec(mesh, b: int, h: int, token_axis: str | None):
    """(B, L, H, D) PartitionSpec on ``mesh``: batch rides ``data`` and
    heads ride ``model`` (Megatron head sharding) whenever divisible —
    otherwise that axis computes replicated — and tokens ride
    ``token_axis`` (``seq`` for the ring kinds, None for local kernels)."""
    from jax.sharding import PartitionSpec as P

    from chiaswarm_tpu.core.mesh import DATA_AXIS, MODEL_AXIS

    sizes = dict(mesh.shape)
    dp, tp = sizes.get(DATA_AXIS, 1), sizes.get(MODEL_AXIS, 1)
    return P(DATA_AXIS if dp > 1 and b % dp == 0 else None,
             token_axis,
             MODEL_AXIS if tp > 1 and h % tp == 0 else None,
             None)


def _try_ring(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, scale: float,
              impl: str) -> jnp.ndarray | None:
    """Sequence-parallel dispatch: shard tokens over the active mesh's
    ``seq`` axis and run the ring — the ppermute scan unless the fused
    ring-flash kernel is named explicitly. None = not eligible.

    The specs compose with the other parallel axes: batch rides ``data``
    and heads ride ``model`` (Megatron head sharding) whenever divisible,
    so a dp x tp x sp mesh needs no resharding beyond the ring itself.
    Per-shard attention inside the ppermute ring is the einsum
    recurrence — local sequences are L/sp, below the flash kernel's win
    threshold; the fused kernel replaces exactly that inner loop with
    the blockwise flash recurrence and overlaps the hop DMA with it."""
    from chiaswarm_tpu.parallel.context import active_seq_mesh

    mesh = active_seq_mesh()
    if mesh is None:
        return None
    b, l, h, _ = q.shape
    if k.shape[1] != l:
        return None  # cross-attention: tiny KV, the einsum path wins
    from chiaswarm_tpu.core.mesh import SEQ_AXIS

    sp = dict(mesh.shape).get(SEQ_AXIS, 1)
    ring_kinds = ("ring", "ring_flash")
    if l % sp or (impl not in ring_kinds and l < _ring_min_tokens()):
        return None
    from functools import partial

    from chiaswarm_tpu.core.compat import shard_map

    spec = _bhd_spec(mesh, b, h, SEQ_AXIS)

    # kind choice inside the ring family: auto keeps the ppermute ring
    # on every platform. The fused kernel (DMA under compute) is engaged
    # explicitly (impl="ring_flash" / CHIASWARM_ATTENTION) by the parity
    # suite, the bisect probe configs and the HLO audit — its remote-DMA
    # grid has zero executions on a chip, and a default must have run.
    if impl == "ring_flash":
        from chiaswarm_tpu.core.compat import shard_map_unchecked

        from chiaswarm_tpu.ops.ring_flash_attention import (
            ring_flash_attention,
        )

        body = partial(ring_flash_attention, axis_name=SEQ_AXIS,
                       scale=scale,
                       mesh_axis_names=tuple(mesh.axis_names))
        # pallas_call has no shard_map replication rule: checking off
        fn = shard_map_unchecked(body, mesh=mesh,
                                 in_specs=(spec, spec, spec),
                                 out_specs=spec)
    else:
        from chiaswarm_tpu.parallel.ring_attention import ring_attention

        body = partial(ring_attention, axis_name=SEQ_AXIS, scale=scale)
        fn = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec)
    return fn(q, k, v)


def _flash(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
           scale: float) -> jnp.ndarray:
    """The local flash kernel. GSPMD cannot partition a Mosaic kernel
    ("Mosaic kernels cannot be automatically partitioned. Please wrap
    the call in a shard_map."), so when the program's params live on a
    multi-device mesh the call is shard_mapped over it with the specs
    _try_ring uses, minus the ring."""
    from chiaswarm_tpu.ops.flash_attention import flash_attention
    from chiaswarm_tpu.parallel.context import active_mesh

    mesh = active_mesh()
    if mesh is None:
        return flash_attention(q, k, v, scale=scale)
    from functools import partial

    from chiaswarm_tpu.core.compat import shard_map_unchecked

    spec = _bhd_spec(mesh, q.shape[0], q.shape[2], None)
    # pallas_call has no shard_map replication rule: checking off
    return shard_map_unchecked(
        partial(flash_attention, scale=scale), mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec)(q, k, v)


def _xla_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   scale: float) -> jnp.ndarray:
    # (B, L, H, D) x (B, S, H, D) -> (B, H, L, S)
    logits = jnp.einsum("blhd,bshd->bhls", q, k,
                        preferred_element_type=jnp.float32)
    weights = jax.nn.softmax(logits * scale, axis=-1).astype(q.dtype)
    return jnp.einsum("bhls,bshd->blhd", weights, v)


def shared_latent_attention(q: jnp.ndarray, keys: jnp.ndarray, n_keys, *,
                            value_width: int, scale: float | None = None
                            ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Rows q (N, W), all behind the first ``n_keys`` (traced, at least
    1) of ``keys`` (S, W), one key/value head whose values are its first
    ``value_width`` columns: latent attention's decode in the absorbed
    form, every row's heads as rows. Returns (the softmax read-out over
    those keys (N, value_width) float32, each row's log-sum-exp (N,)
    float32, by which the caller joins further keys of its own). One
    path, the causal kernel's sweep with all rows at one position
    (ops/causal_flash_attention.py; Pallas interpret mode off the chip):
    the scores stay in VMEM and key blocks past ``n_keys`` are not
    read."""
    from chiaswarm_tpu.ops.causal_flash_attention import (
        shared_latent_attention as sweep,
    )

    return sweep(q, keys, n_keys, value_width=value_width, scale=scale)


def shared_prompt_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                            n_keys, *, scale: float | None = None
                            ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Rows q (R, H, D), all behind the first ``n_keys`` (traced, at
    least 1) of k (S, Hk, D) / v (S, Hk, Dv), H a multiple of Hk:
    grouped-query attention's decode over a prompt the rows share.
    Returns (the softmax read-out over those keys (R, H, Dv) float32,
    each row's log-sum-exp (R, H) float32, by which the caller joins
    further keys of its own). One path, the causal kernel's sweep with a
    key-value head's rows at one position
    (ops/causal_flash_attention.py; Pallas interpret mode off the chip):
    a head's keys and values are read once for all rows, the scores stay
    in VMEM and key blocks past ``n_keys`` are not read."""
    from chiaswarm_tpu.ops.causal_flash_attention import (
        shared_prompt_attention as sweep,
    )

    return sweep(q, k, v, n_keys, scale=scale)


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    scale: float | None = None,
    impl: AttentionImpl = "auto",
    causal: bool = False,
    q_offset=0,
    shared_key: tuple[jnp.ndarray, jnp.ndarray] | None = None,
    window: int | None = None,
) -> jnp.ndarray:
    """Multi-head scaled dot-product attention, (B, L, H, D) layout.

    ``causal``: query ``l`` sits at absolute position ``q_offset + l``
    (``q_offset`` may be traced: one chunk of a longer prefill against
    the cache so far) and sees keys at positions up to its own; key
    slots from ``q_offset + L`` on are not read. One path serves it, the
    causal flash kernel (ops/causal_flash_attention.py; Pallas interpret
    mode off the chip): the einsum masks nothing, the local flash kernel
    block padding only, and the ring kinds rotate whole unmasked shards,
    so any ``impl`` but ``"auto"`` or ``"flash"`` is refused with it
    (``CHIASWARM_ATTENTION`` does not apply). Keys and values may differ
    in head size there, and ``shared_key`` = (q_shared (B, L, H, R),
    k_shared (B, S, R)) adds a key part that every head shares to the
    logits (``scale`` then defaults to ``(D + R) ** -0.5``). Keys and
    values may also have fewer heads than the queries (grouped-query
    attention: query head j reads key-value head j // (H / Hk), whose
    blocks are fetched once for the group), and ``window`` = w hides the
    keys more than w - 1 behind a query (the same kernel body under its
    own operation name, ``window_flash_attention``: a key block wholly
    outside every window of a query block is neither read nor
    scored)."""
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        raise ValueError(f"expected (B, L, H, D) tensors, got {q.shape}")
    if causal:
        if impl not in ("auto", "flash"):
            raise ValueError(f"attention impl {impl!r} has no causal mask; "
                             "causal=True takes 'auto' or 'flash'")
        from chiaswarm_tpu.ops import causal_flash_attention as kernel

        if window is not None:
            if shared_key is not None:
                raise ValueError("a window takes no shared key part")
            return kernel.window_flash_attention(q, k, v, q_offset,
                                                 window=window, scale=scale)
        return kernel.causal_flash_attention(q, k, v, q_offset, shared_key,
                                             scale=scale)
    if shared_key is not None or window is not None:
        raise ValueError("shared_key and window are the causal kernel's "
                         "operands; pass causal=True with them")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    env_forced = False
    if impl == "auto":
        env = _env_impl()
        if env is not None:
            impl, env_forced = env, True

    # low-precision activations (CHIASWARM_ACTIVATIONS, default off):
    # identity when disabled — applied BEFORE the taps so the numerics
    # streams record what the kernels actually consumed
    from chiaswarm_tpu.convert.quantize import fake_quant_activation

    q = fake_quant_activation(q, tag="attn.q")
    k = fake_quant_activation(k, tag="attn.k")
    v = fake_quant_activation(v, tag="attn.v")

    # swarmlens (ISSUE 11): per-call-site I/O probes. ``step`` carries a
    # TRACE-time call index — twin programs trace the same module
    # structure in the same order, so call N aligns across runs (the
    # bisect drill-down from "eps diverged" to "THIS attention layer,
    # and on the input or the output side"; the driver resets the
    # counter between paired runs).
    if _numerics.enabled_for("attn"):
        idx = _numerics.TAPS.trace_seq("attn")
        q = _numerics.tap("attn.q", q, step=idx)
        k = _numerics.tap("attn.k", k, step=idx)
        v = _numerics.tap("attn.v", v, step=idx)

        def _out_tap(out: jnp.ndarray) -> jnp.ndarray:
            return _numerics.tap("attn.out", out, step=idx)
    else:
        def _out_tap(out: jnp.ndarray) -> jnp.ndarray:
            return out

    # sequence-parallel dispatch is orthogonal to the LOCAL impl choice:
    # under an active seq>1 mesh even impl="xla" callers (e.g. a
    # latency_mode worker with use_flash_attention=false) ring their
    # large self-attentions — the guards inside _try_ring keep small
    # sequences on the local paths
    out = _try_ring(q, k, v, scale, impl)
    if out is not None:
        return _out_tap(out)
    if impl in ("ring", "ring_flash"):
        from chiaswarm_tpu.parallel.context import active_seq_mesh

        if active_seq_mesh() is None and not env_forced:
            # explicit impl= is a caller contract; the env knob is
            # advisory (a fleet-wide roll must not crash workers whose
            # mesh has no seq axis — they keep their local paths)
            raise ValueError(
                f"impl={impl!r} requires an active sequence-parallel mesh "
                "(parallel.context.param_mesh)")
        # mesh active but shape not divisible by the seq axis:
        # correctness first, fall through to the local paths
        impl = "auto"

    use_flash = False
    if impl == "flash":
        use_flash = True
    elif impl == "auto":
        # The Pallas kernel from 1024 tokens up; tiny KV (77-token text
        # cross-attention) and small spatial grids stay on the einsum
        # path. The boundary was swept on an installation that is gone
        # and has not been swept on this chip (ROADMAP S3: head sizes
        # under 128 at short KV may belong on the XLA path).
        use_flash = (
            jax.default_backend() == "tpu"
            and q.shape[1] >= 1024
            and k.shape[1] >= 1024
        )

    if use_flash:
        return _out_tap(_flash(q, k, v, scale))
    return _out_tap(_xla_attention(q, k, v, scale))
