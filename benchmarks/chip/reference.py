"""Plain reference of the coded computation the program serves.

Independent of the program: its own Berrut encode and decode matrices
(float64 on the host), and a model in straightforward ``jax.numpy`` with
no cache, no kernels and no batching tricks.  This file holds what every
model shares: the coding, the input embedding and the unembedding
(through the tables the architecture module names), and the products,
norms and rotary embeddings its layers are built from.  The layers
themselves, and the first attention layer's cache, are the architecture
module's (``archs/<arch>.py``, ``spec.arch_module``); each function here
takes that module as its first argument.

One group of K queries is Berrut-encoded position by position into N+1
coded embedding sequences; each runs through the model whole; the coded
logits at each served position are Berrut-decoded over the workers the
round used (those that answered, less the one corrupting it).  Served
tokens are then read against the decoded logits, and the program's
first-layer cache against ``first_layer_kv``.

Numerics (``NUMERICS``): each names the dtype values are stored in and
the matrix precision of two kinds of product: those with the weights
(projections, MLP, unembedding) and those of attention and of the
Berrut coding.  On a TPU a float32 product at ``default`` precision is
one bfloat16 pass; at ``highest`` it is float32.  Norms, softmax and
every product's sum run in float32 in all of them.  ``REFERENCE``,
``"float32"``, is the numerics the configuration states: float32 values,
every product at the default precision, as the program's XLA ops and
Pallas kernels run them.  ``"bfloat16"``, the control, stores every
value in bfloat16.  The other two are for the look in ``calibrate.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from model import Coding

POSITION_BLOCK = 64


# ------------------------------------------------------------ Berrut


def chebyshev_first(k: int) -> np.ndarray:
    return np.cos((2 * np.arange(k) + 1) * np.pi / (2 * k))


def chebyshev_second(n_nodes: int) -> np.ndarray:
    n = n_nodes - 1
    return np.cos(np.arange(n_nodes) * np.pi / n) if n else np.ones(1)


def berrut(points: np.ndarray, nodes: np.ndarray,
           used: np.ndarray) -> np.ndarray:
    """(len(points), len(nodes)) barycentric matrix of Berrut's rational
    interpolant through the ``used`` nodes, with weights alternating in
    sign over the used nodes in their order; a point on a used node
    takes that node's value."""
    idx = np.flatnonzero(used)
    sign = (-1.0) ** np.arange(len(idx))
    out = np.zeros((len(points), len(nodes)))
    for m, z in enumerate(points):
        diff = z - nodes[idx]
        hit = np.abs(diff) < 1e-12
        if hit.any():
            out[m, idx[np.argmax(hit)]] = 1.0
            continue
        t = sign / diff
        out[m, idx] = t / t.sum()
    return out


def encode_matrix(c: Coding) -> np.ndarray:
    """(N+1, K): coded query i = sum_j W[i, j] query_j."""
    alphas, betas = chebyshev_first(c.k), chebyshev_second(c.workers)
    return berrut(betas, alphas, np.ones(c.k, bool))


def decode_matrix(c: Coding, used: np.ndarray) -> np.ndarray:
    """(K, N+1): query j's prediction from the used coded predictions."""
    alphas, betas = chebyshev_first(c.k), chebyshev_second(c.workers)
    return berrut(alphas, betas, used)


# ------------------------------------------------------------ model

# numerics name -> (storage dtype, precision of the products with the
# weights, precision of the attention and coding products)
NUMERICS = {"float32": ("float32", "default", "default"),
            "float32_mixed": ("float32", "default", "highest"),
            "float32_highest": ("float32", "highest", "highest"),
            "bfloat16": ("bfloat16", "default", "default")}
REFERENCE = "float32"


def matmul(eq: str, a, b, dtype, precision: str):
    """float32 product of two operands stored in ``dtype``."""
    return jnp.einsum(eq, a.astype(dtype), b.astype(dtype),
                      precision=precision,
                      preferred_element_type=jnp.float32)


def rms_norm(x, w, eps, out_dtype):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(out_dtype)


def rope(x, cos, sin, out_dtype):
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(out_dtype)


def rotary(dims, t: int):
    inv = 1.0 / dims.rope_theta ** (
        jnp.arange(0, dims.head_dim, 2, dtype=jnp.float32) / dims.head_dim)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]


def coded_inputs(arch, dims, params: dict, tokens: jnp.ndarray,
                 enc: jnp.ndarray, numerics: str) -> jnp.ndarray:
    """(K, T) token ids -> (N+1, T, d) coded input embeddings."""
    store, _, a_prec = NUMERICS[numerics]
    cdt = jnp.dtype(store)
    table = arch.embedding(params).astype(cdt)
    emb = jnp.take(table, tokens, axis=0) * jnp.asarray(dims.embed_mult, cdt)
    return matmul("ik,ktd->itd", enc, emb, cdt, a_prec).astype(cdt)


def decoded_block(arch, params: dict, h: jnp.ndarray, pos: jnp.ndarray,
                  dec: jnp.ndarray, numerics: str) -> jnp.ndarray:
    """Decoded float32 logits (K, B, V) at positions ``pos`` (B,) of the
    coded states ``h`` (N+1, T, d), with one decode matrix per position
    ``dec`` (B, K, N+1)."""
    store, w_prec, a_prec = NUMERICS[numerics]
    cdt = jnp.dtype(store)
    coded = matmul("sbd,vd->sbv", h[:, pos], arch.unembedding(params),
                   cdt, w_prec).astype(cdt)
    return matmul("bks,sbv->kbv", dec, coded, cdt, a_prec).astype(cdt) \
        .astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 1, 5))
def _states(arch, dims, params, tokens, enc, numerics):
    x = coded_inputs(arch, dims, params, tokens, enc, numerics)
    return arch.hidden_states(dims, params, x, numerics)


@functools.partial(jax.jit, static_argnums=(0, 1, 5))
def first_layer_kv(arch, dims, params, tokens, enc, numerics):
    """(K, T) token ids -> the first attention layer's cached keys and
    values (N+1, T, kv_heads, head_dim) of the coded streams, in
    float32."""
    x = coded_inputs(arch, dims, params, tokens, enc, numerics)
    k, v = arch.first_kv(dims, params, x, numerics)
    return k.astype(jnp.float32), v.astype(jnp.float32)


_decoded = jax.jit(decoded_block, static_argnums=(0, 5))


@jax.jit
def _gaps(ref, ids):
    """(K, B) gaps of the ids (K, B) below the best of ``ref`` (K, B, V)."""
    got = jnp.take_along_axis(ref, ids[..., None], axis=-1)[..., 0]
    return jnp.max(ref, axis=-1) - got


def group_gaps(arch, dims, c: Coding, params: dict, tokens: np.ndarray,
               used: np.ndarray, served: np.ndarray, length: int,
               references=(REFERENCE,), controls=()) -> dict:
    """Gaps of one group, by (who, reference).

    tokens:     (K, T) the rows' fed ids (prompt, then the ids served
      back to the rows), T <= ``length``;
    used:       (J, N+1) workers each served position's round decoded
      from;
    served:     (K, J) the ids served at the J positions after the prompt;
    references: numerics (``NUMERICS``) to read the gaps against;
    controls:   numerics to run in the program's place.

    Returns {(who, reference): (K, J) gaps below that reference's best},
    who being ``"program"`` (the served ids) or a control (the id it
    ranks first)."""
    k, t = tokens.shape
    j = served.shape[1]
    prompt = t - j + 1
    padded = np.zeros((k, length), np.int32)
    padded[:, :t] = tokens
    enc = jnp.asarray(encode_matrix(c), jnp.float32)
    states = {n: _states(arch, dims, params, jnp.asarray(padded), enc, n)
              for n in dict.fromkeys(tuple(references) + tuple(controls))}
    out = {(w, r): np.zeros((k, j)) for w in ("program", *controls)
           for r in references}
    for lo in range(0, j, POSITION_BLOCK):
        idx = np.arange(lo, lo + POSITION_BLOCK)
        live = idx < j
        idx = np.where(live, idx, j - 1)
        pos = jnp.asarray(prompt - 1 + idx, jnp.int32)
        dec = jnp.asarray(np.stack([decode_matrix(c, used[i]) for i in idx]),
                          jnp.float32)
        logits = {n: _decoded(arch, params, h, pos, dec, n)
                  for n, h in states.items()}
        ids = {"program": jnp.asarray(served[:, idx], jnp.int32)}
        ids.update({w: jnp.argmax(logits[w], axis=-1).astype(jnp.int32)
                    for w in controls})
        for (w, r), gap in out.items():
            gap[:, idx[live]] = np.asarray(_gaps(logits[r], ids[w]))[:, live]
    return out
