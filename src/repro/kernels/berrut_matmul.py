"""Pallas TPU kernels: fused Berrut coded encode/decode contraction.

The ApproxIFER hot path applies a small (O, I) barycentric matrix to a
huge feature tensor: encode O=N+1, I=K; decode O=K, I=N+1 (O, I <= ~64).
This is a skinny matmul with extreme feature-dim reuse: the whole weight
tile lives in VMEM (even SMEM-sized) while feature tiles stream
HBM -> VMEM once.  Tiling: feature dim in 512-wide lanes (128-aligned,
rounded up and padded for ragged feature dims so a huge unaligned F can
never become one VMEM-busting tile); groups on the grid's leading axis;
fp32 accumulation.

Two entry points:
  * ``berrut_apply`` — the plain group-major contraction,
    (O, I) @ (..., I, F) -> (..., O, F).
  * ``berrut_encode_dispatch`` — encode fused with the worker-major
    stream layout of the mesh pool (DESIGN.md §13): each grid cell
    writes its (O, ft) tile straight into the (O, G, F) block whose flat
    ``n*G + g`` reshape is the per-rank dispatch layout, so the
    swapaxes/reshape pass over HBM that used to follow the encode
    disappears.

ops.py dispatches here on TPU; tests run interpret=True against
ref.berrut_apply_ref / ref.berrut_encode_dispatch_ref.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

FEATURE_TILE = 512


def _feature_tile(f: int) -> int:
    """Feature tile width: FEATURE_TILE-clamped and 128-lane-aligned.

    A ragged f (f % 128 != 0) rounds UP to the next 128 multiple and the
    operand is padded — never "whole dim as one tile", which at vocab
    scale (f ~ 150k) would blow VMEM.
    """
    if f % 128 == 0:
        return min(FEATURE_TILE, f)
    return min(FEATURE_TILE, ((f + 127) // 128) * 128)


def _kernel(w_ref, x_ref, o_ref):
    # w: (O, I) fp32;  x: (I, FT);  o: (O, FT)
    w = w_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.dot(
        w, x, preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def berrut_apply(weights: jnp.ndarray, x: jnp.ndarray,
                 interpret: bool = False) -> jnp.ndarray:
    """(O, I) @ (..., I, F) -> (..., O, F) with fp32 accumulation.

    Matches ref.berrut_apply_ref for any leading batch dims.
    """
    o_dim, i_dim = weights.shape
    lead = x.shape[:-2]
    f = x.shape[-1]
    xg = x.reshape((-1, i_dim, f))
    g = xg.shape[0]

    ft = _feature_tile(f)
    pad_f = (-f) % ft
    if pad_f:
        xg = jnp.pad(xg, ((0, 0), (0, 0), (0, pad_f)))
    fp = f + pad_f

    grid = (g, fp // ft)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((o_dim, i_dim), lambda gi, fi: (0, 0)),
            pl.BlockSpec((None, i_dim, ft), lambda gi, fi: (gi, 0, fi)),
        ],
        out_specs=pl.BlockSpec((None, o_dim, ft),
                               lambda gi, fi: (gi, 0, fi)),
        out_shape=jax.ShapeDtypeStruct((g, o_dim, fp), x.dtype),
        interpret=interpret,
    )(weights.astype(jnp.float32), xg)
    if pad_f:
        out = out[..., :f]
    return out.reshape(*lead, o_dim, f)


@functools.partial(jax.jit, static_argnames=("interpret",))
def berrut_encode_dispatch(weights: jnp.ndarray, x: jnp.ndarray,
                           interpret: bool = False) -> jnp.ndarray:
    """One-pass encode -> worker-major dispatch.

    (O, I) @ (G, I, F) -> (O*G, F) flat coded streams in the ``n*G + g``
    order the "worker" mesh axis shards (a contiguous 1/W slice of the
    output = one worker rank's streams).  Matches
    ref.berrut_encode_dispatch_ref up to float rounding.
    """
    o_dim, i_dim = weights.shape
    g, _, f = x.shape

    ft = _feature_tile(f)
    pad_f = (-f) % ft
    xg = jnp.pad(x, ((0, 0), (0, 0), (0, pad_f))) if pad_f else x
    fp = f + pad_f

    nf = fp // ft
    # The (O, G, F) worker-major block viewed as (O, G*F): each grid cell
    # writes its (O, FT) tile at column block g*nf + fi — whole O rows and
    # 128-aligned lanes, so the block tiles on the TPU, and the encode
    # contraction and the dispatch transpose stay one HBM pass.
    out = pl.pallas_call(
        _kernel,
        grid=(g, nf),
        in_specs=[
            pl.BlockSpec((o_dim, i_dim), lambda gi, fi: (0, 0)),
            pl.BlockSpec((None, i_dim, ft), lambda gi, fi: (gi, 0, fi)),
        ],
        out_specs=pl.BlockSpec((o_dim, ft), lambda gi, fi: (0, gi * nf + fi)),
        out_shape=jax.ShapeDtypeStruct((o_dim, g * fp), x.dtype),
        interpret=interpret,
    )(weights.astype(jnp.float32), xg)
    out = out.reshape(o_dim, g, fp)
    if pad_f:
        out = out[..., :f]
    return out.reshape(o_dim * g, f)
