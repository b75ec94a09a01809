"""Weights from the seed: made by the benchmark, given to the program
and to the plain reference alike (the reference takes nothing the
program has made).

A leaf's values depend only on (seed, leaf name, shape): matrices are
uniform with standard deviation 0.02, 1-D gains (the rmsnorm weights)
1 + uniform with standard deviation 0.05, drawn with the device's own
bit generator (``impl="rbg"``: jax's default threefry took about 25 s
for the 2.9 B leaves of the serving model on a v5e) and rounded to
bfloat16 — the type they are served in, and the type the
train step holds them in beside its f32 masters — so an f32 upcast of a
leaf is exact.  ``make_all`` makes every leaf in ONE jitted call on the
device and takes the arrays it replaces as donated arguments, so a
model's weights are rewritten in place; ``make_leaf`` re-makes one leaf
bit for bit for the reference, which walks the model a layer at a time.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def root_key(seed: int):
    return jax.random.key(int(seed), impl="rbg")


def leaf_values(key, name: str, shape, dtype):
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    z = jax.random.uniform(k, tuple(shape), jnp.float32, -1.0, 1.0) * 3 ** 0.5
    v = 1.0 + 0.05 * z if len(shape) == 1 else 0.02 * z
    return v.astype(jnp.bfloat16).astype(dtype)


def make_all(seed: int, names, old_arrays):
    """New values for every leaf of ``names`` with the shapes and dtypes
    of ``old_arrays``, which are donated (deleted) by the call."""
    shapes = tuple(tuple(a.shape) for a in old_arrays)
    dtypes = tuple(a.dtype for a in old_arrays)
    names = tuple(names)

    def make(old, key):
        del old
        return [leaf_values(key, n, s, d)
                for n, s, d in zip(names, shapes, dtypes)]

    fn = jax.jit(make, donate_argnums=0, keep_unused=True)
    return fn(list(old_arrays), root_key(seed))


_make_leaf = jax.jit(leaf_values, static_argnums=(1, 2, 3))


def make_leaf(seed: int, name: str, shape, dtype=jnp.float32):
    return _make_leaf(root_key(seed), name, tuple(shape), jnp.dtype(dtype))
