"""Seeded weights, made on the device in one jitted call, in the dtype they
are served in.

``init_params`` of the program builds an f32 master stack under ``vmap``
(15 GB at these widths) and ``chip_smoke.py`` fills leaf by leaf with
normals (29 s for 4.5e9 elements on a v5e; PERF.md, PR 21). Here the
hardware bit generator's raw 16-bit words become a uniform of the same
variance, 1/fan_in, and the layer-stacked leaves are made a layer at a time
under ``lax.map``, so the transient is one layer's bits and never a second
copy of the stack.
"""

from __future__ import annotations

import math


def fill_program(init_params, config, shardings=None):
    """The jitted program ``seed -> params``: the tree
    ``init_params(config, key)`` would return, every matrix a seeded
    uniform of variance 1/fan_in in ``config.dtype`` and every norm scale
    ones. ``shardings`` (a matching tree) places each leaf as it is made,
    so no device ever holds another's shard."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    shapes = jax.eval_shape(
        lambda key: init_params(config, key), jax.random.PRNGKey(0)
    )
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    dtype = config.dtype

    def uniform(key, shape, fan_in):
        bits = jax.random.bits(key, shape, dtype=jnp.uint16)
        # U(-a, a) has variance a^2/3
        scale = math.sqrt(3.0 / fan_in) / 32768.0
        return ((bits.astype(jnp.float32) - 32767.5) * scale).astype(dtype)

    def fill(seed):
        # the hardware bit generator: threefry's integer rounds took ~110 s
        # for 4.5e9 elements on a v5e (PERF.md, PR 21)
        key = jax.random.key(seed, impl="rbg")
        out = []
        for i, (path, leaf) in enumerate(leaves):
            name = path[-1].key
            if name.startswith("ln"):
                out.append(jnp.ones(leaf.shape, dtype))
                continue
            fan_in = leaf.shape[-1] if name == "embed" else leaf.shape[-2]
            leaf_key = jax.random.fold_in(key, i)
            if path[0].key == "layers":  # [n_layers, ...]: a layer at a time
                out.append(lax.map(
                    lambda k, shape=leaf.shape[1:], fan_in=fan_in: uniform(
                        k, shape, fan_in
                    ),
                    jax.random.split(leaf_key, leaf.shape[0]),
                ))
            else:
                out.append(uniform(leaf_key, leaf.shape, fan_in))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(fill, out_shardings=shardings)


def seeded_params(init_params, config, seed: int, shardings=None):
    """``fill_program`` run once for ``seed``."""
    import numpy as np

    return fill_program(init_params, config, shardings)(np.uint32(seed))
