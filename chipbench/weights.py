"""The benchmark's weights: made from the seed on the device in one jitted
call, in the dtype they are served in, with the program's parameter
layout (taken from its shapes, never from its values).

Every matrix is drawn from N(0, initializer_range**2), the published
configuration's own initializer, and every norm weight is one.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def jax_key(seed: int):
    """A PRNG key for any whole-number seed (the seed may exceed 32
    bits)."""
    return jax.random.PRNGKey(
        int(np.random.default_rng(seed).integers(2 ** 31)))


def make(shapes, init_range: float, seed: int):
    """Weights with the structure, shapes and dtypes of ``shapes`` (a
    pytree of ``jax.ShapeDtypeStruct``)."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        keys = jax.random.split(key, len(paths))
        out = []
        for k, (path, s) in zip(keys, paths):
            name = str(getattr(path[-1], "key", path[-1]))
            if name.endswith("norm"):
                out.append(jnp.ones(s.shape, s.dtype))
            else:
                out.append((jax.random.normal(k, s.shape, s.dtype)
                            * jnp.asarray(init_range, s.dtype)))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(jax_key(seed))
