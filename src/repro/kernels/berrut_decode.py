"""Pallas TPU kernel: fused coded-round locate+decode tail.

The tail of every coded round turns the (G, N+1, V) coded-logit block
into (G, K, V) decoded logits.  The pre-PR XLA path paid for it three
times over: ``locate`` upcast the WHOLE block to float32 just to read
C_vote strided columns, the per-group Berrut decode matrices were
materialised as (G, K, N+1) HBM tensors, and the contraction ran as a
separate vmapped matmul.  This kernel fuses all of it into one pass over
the block, tiled along the vocab axis in VMEM:

  * the survivor-weight decode matrix of each group is rebuilt from its
    (N+1,) availability mask INSIDE the kernel (rank-based alternating
    signs + barycentric basis with exact node-hit resolution, matching
    ``core.berrut.survivor_weights`` / ``basis_matrix`` op for op), so
    the per-group matrices never touch HBM;
  * the float32 upcast happens per VMEM tile — the full-precision copy
    of the block is never materialised;
  * with ``c_vote > 0`` the kernel also emits the locator's strided
    vote-coordinate columns as a second output of the SAME pass, so a
    caller that decodes at availability masks gets the locate gather
    for free instead of casting the whole (G, N+1, V) block.  (The
    serving tail itself locates BEFORE its masked decode, so it gathers
    via ``error_locator.gather_vote_values`` and uses this kernel for
    the decode alone.)

Masks may be (N+1,) — one shared availability for every group — or
(G, N+1) per-group exclusion masks (rounds where the locator actually
confirmed a Byzantine worker).

ops.py dispatches here on TPU; tests run interpret=True against
ref.fused_group_decode_ref.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Shared with the production matrix construction: systematic node sets
# rely on exact-hit rows decoding as one-hot at the same tolerance.
from repro.core.berrut import _NODE_HIT_TOL

FEATURE_TILE = 512


def _decode_matrix(m: jnp.ndarray, alphas: jnp.ndarray,
                   betas: jnp.ndarray) -> jnp.ndarray:
    """(1, N+1) mask -> (K, N+1) fp32 decode matrix, all in registers.

    Same op sequence as ``berrut.survivor_weights`` + ``basis_matrix``
    (the jnp reference), with the cumulative survivor rank computed as a
    matmul against a constant triangular matrix (TPU-friendly — no 1-D
    cumsum inside the kernel).
    """
    n1 = m.shape[-1]
    le = (jax.lax.broadcasted_iota(jnp.int32, (n1, n1), 0)
          <= jax.lax.broadcasted_iota(jnp.int32, (n1, n1), 1))
    rank = jnp.dot(m, le.astype(jnp.float32),
                   preferred_element_type=jnp.float32) - 1.0   # (1, N+1)
    sign = 1.0 - 2.0 * jnp.mod(rank, 2.0)
    w = sign * m                                               # (1, N+1)
    diff = alphas - betas                                      # (K, N+1)
    raw_hit = jnp.abs(diff) < _NODE_HIT_TOL
    safe = jnp.where(raw_hit, 1.0, diff)
    hit = jnp.logical_and(raw_hit, m > 0.0)
    terms = w / safe
    denom = jnp.sum(terms, axis=-1, keepdims=True)
    basis = terms / denom
    row_hit = jnp.any(hit, axis=-1, keepdims=True)
    return jnp.where(row_hit, hit.astype(jnp.float32), basis)


def _make_kernel(stride: int, gather: bool):
    def kernel(m_ref, a_ref, b_ref, x_ref, o_ref, *maybe_c):
        # the whole (G, N+1) mask block sits in VMEM (a (1, N+1) block of
        # it is not (8, 128)-tileable); select this group's row exactly —
        # one nonzero term per column
        masks = m_ref[...]
        rows = jax.lax.broadcasted_iota(jnp.int32, masks.shape, 0)
        m = jnp.sum(jnp.where(rows == pl.program_id(0), masks, 0.0),
                    axis=0, keepdims=True)                   # (1, N+1)
        dec = _decode_matrix(m, a_ref[...], b_ref[...])
        xt = x_ref[0].astype(jnp.float32)                  # (N+1, FT)
        o_ref[0] = jnp.dot(dec, xt,
                           preferred_element_type=jnp.float32
                           ).astype(o_ref.dtype)
        if gather:
            maybe_c[0][0] = xt[:, ::stride]                # (N+1, FT/stride)
    return kernel


def gather_layout(v: int, c_vote: int, ft: int, pad_f: int):
    """Can the vote-coordinate gather ride the decode pass?

    The coordinate scheme comes from ``error_locator.vote_layout`` (the
    single definition — coords = arange(C) * stride); the fused gather
    additionally needs every vocab tile to contain the same number of
    them and no coordinate to fall into the divisibility padding.
    Returns (stride, coords_per_tile) or None (caller gathers outside
    the kernel, still before the upcast).
    """
    if c_vote <= 0:
        return None
    from repro.core.error_locator import vote_layout
    c, stride = vote_layout(v, c_vote)
    if pad_f or ft % stride or c * stride != v:
        return None
    return stride, ft // stride


@functools.partial(jax.jit,
                   static_argnames=("c_vote", "interpret"))
def fused_group_decode(grouped: jnp.ndarray, masks: jnp.ndarray,
                       alphas: jnp.ndarray, betas: jnp.ndarray, *,
                       c_vote: int = 0, interpret: bool = False):
    """(G, N+1, V) block + masks -> (G, K, V) decoded logits.

    masks: (N+1,) shared availability or (G, N+1) per-group exclusion.
    With ``c_vote > 0`` also returns the (G, N+1, C) float32 vote-
    coordinate gather from the same pass.
    """
    g, n1, v = grouped.shape
    k = alphas.shape[0]
    m2 = jnp.broadcast_to(masks.astype(jnp.float32), (g, n1))

    ft = min(FEATURE_TILE, v) if v % 128 == 0 else v
    pad_f = (-v) % ft
    xg = grouped
    if pad_f:
        xg = jnp.pad(xg, ((0, 0), (0, 0), (0, pad_f)))
    fp = v + pad_f

    layout = gather_layout(v, c_vote, ft, pad_f)
    in_kernel_gather = c_vote > 0 and layout is not None
    stride, cpt = layout if in_kernel_gather else (1, 1)

    grid = (g, fp // ft)
    in_specs = [
        pl.BlockSpec((g, n1), lambda gi, fi: (0, 0)),
        pl.BlockSpec((k, 1), lambda gi, fi: (0, 0)),
        pl.BlockSpec((1, n1), lambda gi, fi: (0, 0)),
        pl.BlockSpec((1, n1, ft), lambda gi, fi: (gi, 0, fi)),
    ]
    out_shape = [jax.ShapeDtypeStruct((g, k, fp), grouped.dtype)]
    out_specs = [pl.BlockSpec((1, k, ft), lambda gi, fi: (gi, 0, fi))]
    if in_kernel_gather:
        c = min(v, c_vote)
        out_shape.append(jax.ShapeDtypeStruct((g, n1, c), jnp.float32))
        out_specs.append(
            pl.BlockSpec((1, n1, cpt), lambda gi, fi: (gi, 0, fi)))

    outs = pl.pallas_call(
        _make_kernel(stride, in_kernel_gather),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(m2, alphas.astype(jnp.float32).reshape(k, 1),
      betas.astype(jnp.float32).reshape(1, n1), xg)

    decoded = outs[0][..., :v] if pad_f else outs[0]
    if c_vote <= 0:
        return decoded
    if in_kernel_gather:
        return decoded, outs[1]
    # misaligned vote layout: gather outside the kernel — but still from
    # the raw block, BEFORE any float32 upcast
    from repro.core.error_locator import gather_vote_values
    return decoded, gather_vote_values(grouped, c_vote)
