"""Weights for a run, made on the device from the seed in one jitted call.

The tree's layout and leaf dtypes are the program's (``jax.eval_shape``
of its ``init``), so weights are served in the type the program keeps
them in; the values are the benchmark's own, chosen by leaf name:
norm scales 1 + 0.1 N(0, 1) (so a scale that is not read shows in the
outputs), embedding tables 0.02 N(0, 1), every other matrix
N(0, 1) / sqrt(fan_in), and any other vector zero.
"""
from __future__ import annotations


def make_params(model, seed: int):
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(seed)
    shapes = jax.eval_shape(model.init, key)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        leaves = []
        for i, (path, s) in enumerate(flat):
            name = jax.tree_util.keystr(path)
            k = jax.random.fold_in(key, i)
            if name.endswith("['scale']"):
                v = 1.0 + 0.1 * jax.random.normal(k, s.shape, jnp.float32)
            elif name.endswith("['table']"):
                v = 0.02 * jax.random.normal(k, s.shape, jnp.float32)
            elif len(s.shape) >= 2:
                v = jax.random.normal(k, s.shape, jnp.float32) \
                    / jnp.sqrt(jnp.float32(s.shape[-2]))
            else:
                v = jnp.zeros(s.shape, jnp.float32)
            leaves.append(v.astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(key)
