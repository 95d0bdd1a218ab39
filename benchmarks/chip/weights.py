"""Random weights from the seed, made on the device in one jitted call.

The benchmark makes the weights, not the program: the reference reads
the same arrays and nothing the program made.  They are laid out as the
program's dense decoder takes them (one run of stacked layers), in the
dtype the configuration serves.  Norm scales are drawn around 1 so the
comparison with the reference covers them too.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from model import Dims


def _normal(key, shape, std):
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                             jnp.float32)


def _scale(key, shape):
    return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)


EMBED_STD = 0.01


def init(dims: Dims, key) -> dict:
    n, d, h, kv, hd, f, v = (dims.layers, dims.hidden, dims.heads,
                             dims.kv_heads, dims.head_dim, dims.ffn,
                             dims.vocab)
    ks = iter(jax.random.split(key, 16))
    tree = {
        "embeddings": {"embed": _normal(next(ks), (v, d), EMBED_STD)},
        "blocks": {"runs": [{
            "norm1": {"scale": _scale(next(ks), (n, d))},
            "attn": {
                "wq": _normal(next(ks), (n, d, h, hd), d ** -0.5),
                "wk": _normal(next(ks), (n, d, kv, hd), d ** -0.5),
                "wv": _normal(next(ks), (n, d, kv, hd), d ** -0.5),
                "wo": _normal(next(ks), (n, h, hd, d), (h * hd) ** -0.5),
                "q_norm": _scale(next(ks), (n, hd)),
                "k_norm": _scale(next(ks), (n, hd)),
            },
            "norm2": {"scale": _scale(next(ks), (n, d))},
            "mlp": {
                "w_gate": _normal(next(ks), (n, d, f), d ** -0.5),
                "w_in": _normal(next(ks), (n, d, f), d ** -0.5),
                "w_out": _normal(next(ks), (n, f, d), f ** -0.5),
            },
        }]},
        "final_norm": {"scale": _scale(next(ks), (d,))},
    }
    return jax.tree.map(lambda x: x.astype(dims.dtype), tree)


def make(dims: Dims, seed: int) -> dict:
    return jax.jit(init, static_argnums=0)(dims, jax.random.PRNGKey(seed))


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
