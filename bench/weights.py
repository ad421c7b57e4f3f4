"""Seeded random weights, made on the device in one jitted call.

The benchmark makes the weights and hands them to the system under test;
the reference reads the same arrays.  Only the tree's structure (leaf paths,
shapes, dtypes) comes from the program.  Each leaf is drawn from the seed
folded with its own path, by a rule for its name:

  ``scale`` (norm gains), ``D``           ones
  ``b``, ``conv_b``                      zeros
  ``A_log``                              log of U(1, 16)       (Mamba2)
  ``dt_bias``                            softplus^-1 of log-U(1e-3, 1e-1)
  ``conv_w``                             U(-1/sqrt(k), 1/sqrt(k))
  ``emb``                                N(0, 0.02^2)
  ``w`` (every projection)               N(0, 1 / fan_in)
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def root_key(seed: int):
    """A JAX key from any non-negative integer seed (wider than 32 bits)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _path(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def _leaf(key, name: str, shape, dtype):
    last = name.rsplit("/", 1)[-1]
    f32 = jnp.float32
    if last in ("scale", "D"):
        v = jnp.ones(shape, f32)
    elif last in ("b", "conv_b"):
        v = jnp.zeros(shape, f32)
    elif last == "A_log":
        v = jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    elif last == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, np.log(1e-3),
                                        np.log(1e-1)))
        v = dt + jnp.log(-jnp.expm1(-dt))
    elif last == "conv_w":
        lim = shape[-2] ** -0.5
        v = jax.random.uniform(key, shape, f32, -lim, lim)
    elif last == "emb":
        v = 0.02 * jax.random.normal(key, shape, f32)
    elif last == "w":
        v = jax.random.normal(key, shape, f32) * shape[-2] ** -0.5
    else:
        raise ValueError(f"no weight rule for leaf {name!r}")
    return v.astype(dtype)


def make_params(shapes, seed: int):
    """Concrete weights for the ``jax.ShapeDtypeStruct`` tree ``shapes``."""
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_path(p) for p, _ in flat]

    @jax.jit
    def build(key):
        leaves = [_leaf(jax.random.fold_in(key, zlib.crc32(n.encode())), n,
                        s.shape, s.dtype) for n, (_, s) in zip(names, flat)]
        return jax.tree_util.tree_unflatten(tree, leaves)

    return build(root_key(seed))
