"""Seeded weights, made on the device in one jitted call.

The benchmark is the checkpoint: the program is handed these arrays in
place of a loaded one, and the plain reference reads the same arrays. The
only thing taken from the program is the checkpoint LAYOUT (the abstract
shape tree of its modules), as a converter would take it.

Scaling is the one a denoise of hundreds of layers needs to stay finite
in bfloat16: kernels uniform with variance 1/fan_in, norm gains one,
everything else (biases, embedding tables) uniform with standard
deviation 0.02. Every leaf is non-zero, so no broken kernel hides behind
a zero projection. A value is the top 16 bits of a hashed counter
(bfloat16 keeps 8).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _leaf_std(path, shape) -> float | None:
    """None = a norm gain (ones)."""
    name = getattr(path[-1], "key", None) if path else None
    if name == "scale":
        return None
    if name == "kernel" and len(shape) >= 2:
        return 1.0 / math.sqrt(math.prod(shape[:-1]))
    return 0.02


_GOLDEN, _MIX1, _MIX2 = 0x9E3779B1, 0x85EBCA6B, 0xC2B2AE35


def _hashed_bits(n: int, salt):
    """uint32 per element: the murmur3 finalizer over a counter. Plain
    element-wise work, so the whole fill is one cheap program (a
    hardware RNG op a leaf compiled for 12 minutes on the v5e)."""
    x = jax.lax.iota(jnp.uint32, n) * jnp.uint32(_GOLDEN) + salt
    x = (x ^ (x >> 16)) * jnp.uint32(_MIX1)
    x = (x ^ (x >> 13)) * jnp.uint32(_MIX2)
    return x ^ (x >> 16)


def fill_fn(shape_tree, dtype: str = "bfloat16"):
    """seed words (2,) uint32 -> list of leaves of ``shape_tree``."""
    out_dtype = jnp.dtype(dtype)
    paths_leaves, _ = jax.tree_util.tree_flatten_with_path(shape_tree)

    def fill(seed_words):
        base = _hashed_bits(2, seed_words[0])[1] ^ seed_words[1]
        leaves = []
        for i, (path, spec) in enumerate(paths_leaves):
            leaf_dtype = (out_dtype if spec.dtype == jnp.float32
                          else spec.dtype)
            std = _leaf_std(path, spec.shape)
            if std is None:
                leaves.append(jnp.ones(spec.shape, leaf_dtype))
                continue
            bits = _hashed_bits(math.prod(spec.shape),
                                base + jnp.uint32((i * _MIX1) & 0xFFFFFFFF))
            unit = ((bits >> 16).astype(jnp.float32) + 0.5) / 65536.0 - 0.5
            leaves.append((unit * (std * math.sqrt(12.0))
                           ).astype(leaf_dtype).reshape(spec.shape))
        return leaves

    return fill


def seed_words(seed: int):
    seed = int(seed) % (2 ** 64)
    return jnp.asarray([seed & 0xFFFFFFFF, seed >> 32], jnp.uint32)


def make_params(shape_tree, seed: int, dtype: str = "bfloat16", device=None):
    """Fill ``shape_tree`` (a pytree of ShapeDtypeStruct) from ``seed``."""
    treedef = jax.tree_util.tree_structure(shape_tree)
    fill = fill_fn(shape_tree, dtype)
    jitted = jax.jit(fill) if device is None else jax.jit(
        fill, out_shardings=jax.sharding.SingleDeviceSharding(device))
    return jax.tree_util.tree_unflatten(treedef, jitted(seed_words(seed)))
