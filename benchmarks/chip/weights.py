"""Random weights from the seed, made on the device in one jitted call.

The benchmark makes the weights, not the program: the reference reads
the same arrays and nothing the program made.  The configuration's
architecture module (``archs/<arch>.py``) draws them, laid out as the
program's decoder takes them, from the helpers below; they are made in
the dtype the configuration serves.  Norm scales are drawn around 1 so
the comparison with the reference covers them too.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EMBED_STD = 0.01


def normal(key, shape, std):
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                             jnp.float32)


def norm_scale(key, shape):
    return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)


def make(arch, dims, seed: int) -> dict:
    """``arch.init``'s tree from ``seed``, in ``dims.dtype``."""
    def init(key):
        return jax.tree.map(lambda x: x.astype(dims.dtype),
                            arch.init(dims, key))
    return jax.jit(init)(jax.random.PRNGKey(seed))


def check_layout(params: dict, program_shapes) -> None:
    """Fail unless ``params`` has the structure, shapes and dtypes of the
    program's own parameter tree (``program_shapes``: its eval_shape)."""
    mine = jax.tree.structure(params)
    theirs = jax.tree.structure(program_shapes)
    if mine != theirs:
        raise ValueError(f"weight tree {mine} is not the program's "
                         f"{theirs}")
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(program_shapes)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"weight {a.shape} {a.dtype} is not the "
                             f"program's {b.shape} {b.dtype}")
