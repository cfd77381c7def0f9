"""Weights from the seed, made on the device in one jitted call, in the type
they are served in.

The benchmark, not the program, makes the weights, so that the reference
can read the same ones without taking anything the program made. Only the
tree's shapes come from the program (``jax.eval_shape`` of its
initialiser). Every matrix is normal with standard deviation 0.02; every
norm scale, stored as an offset from one, is normal with standard
deviation 0.1, so that a reference that read the scale the wrong way
would disagree. Stacked per-layer leaves are drawn one layer at a time, so
no full-size float32 copy of a leaf is ever held."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

MATRIX_STD = 0.02
NORM_STD = 0.1


def seed_words(seed: int) -> np.ndarray:
    """Two 32-bit words from a seed of any size (seeds may exceed 2**31)."""
    return np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)


def _is_norm(path) -> bool:
    return any("norm" in str(getattr(k, "key", "")) for k in path)


def _draw(key, shape, dtype, std: float, stacked: bool):
    def one(k, shp):
        return (jax.random.normal(k, shp, jnp.float32) * std).astype(dtype)

    if stacked and len(shape) >= 2:
        keys = jax.random.split(key, shape[0])
        return jax.lax.map(lambda k: one(k, shape[1:]), keys)
    return one(key, shape)


def maker(shapes):
    """A jitted ``make(words) -> params`` for the tree ``shapes`` (a pytree
    of ``jax.ShapeDtypeStruct``): one compiled program serves every seed."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def make(words):
        base = jax.random.wrap_key_data(words, impl="threefry2x32")
        out = []
        for i, (path, s) in enumerate(leaves):
            stacked = str(getattr(path[0], "key", "")) == "periods"
            std = NORM_STD if _is_norm(path) else MATRIX_STD
            out.append(_draw(jax.random.fold_in(base, i), s.shape, s.dtype,
                             std, stacked))
        return jax.tree_util.tree_unflatten(treedef, out)

    return make
