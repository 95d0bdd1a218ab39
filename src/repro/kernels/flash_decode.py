"""Pallas TPU kernels: single-token decode attention over a long KV cache.

The decode_32k / long_500k hot spot: one query row per stream against a
32k-512k cache.  The cache length is the tiled (streamed) dimension; fp32
online-softmax state lives in VMEM scratch.  Mosaic requires the last two
dims of a block to be (8, 128)-aligned or whole, so every cache block
carries the WHOLE ``(kv_heads, head_dim)`` minor dims and the kernel loops
over KV heads with static indices — each cache tile streams HBM->VMEM
once per stream.  GQA: the ``rep`` q-heads sharing a KV head ride the
sublane dim so the (rep, KT) score matmul feeds the MXU.

Two variants:
  * ``flash_decode`` — shared-depth decode with an explicit (B, W)
    ``kv_mask`` of live ring-buffer slots, matching
    ref.decode_attention_ref.
  * ``pool_flash_decode`` — the continuous-batching slot pool
    (DESIGN.md §10): per-stream ``(B,)`` ring positions and an optional
    per-stream slot-live mask are scalar-prefetched into SMEM and the
    validity of every KV tile is derived IN-KERNEL (``kvpos <= pos``,
    composed with ``live``), so the caller never materialises a (B, W)
    mask or full-width masked scores.  Matches
    ref.pool_decode_attention_ref up to float rounding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
KV_TILE = 512


def kv_tile(w: int) -> int:
    """Cache tile length: ``KV_TILE``, or the whole ring when it is
    shorter (a short serving cache is one tile, never padded to 512)."""
    return min(KV_TILE, w)


def _nt_dot(a, b):
    """a @ b.T with fp32 accumulation (contract the last dims)."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _update(q_ref, k_ref, v_ref, ok, m_scr, l_scr, acc_scr, *,
            softcap: float, scale: float, kv_scale: float):
    """One KV tile of online softmax for every KV head of one stream.

    q_ref (KV, rep, D); k_ref/v_ref (KT, KV, D); ok (1, KT) validity."""
    for g in range(k_ref.shape[1]):
        q = q_ref[g].astype(jnp.float32) * scale            # (rep, D)
        k = k_ref[:, g, :].astype(jnp.float32)              # (KT, D)
        v = v_ref[:, g, :].astype(jnp.float32)              # (KT, D)
        if kv_scale > 0.0:
            # int8 KV cache: dequantise per block IN VMEM — HBM traffic
            # stays at the int8 byte count (the decode memory-term lever,
            # EXPERIMENTS.md §5.3 iter 1)
            k = k / kv_scale
            v = v / kv_scale
        s = _nt_dot(q, k)                                   # (rep, KT)
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[g]                                   # (rep, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        m_safe = jnp.where(m_new <= NEG_INF, 0.0, m_new)
        p = jnp.exp(s - m_safe)
        p = jnp.where(ok, p, 0.0)
        alpha = jnp.where(m_prev <= NEG_INF, 0.0, jnp.exp(m_prev - m_safe))
        l_scr[g] = l_scr[g] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_scr[g] = acc_scr[g] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[g] = m_new


def _init(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _finalize(o_ref, l_scr, acc_scr):
    for g in range(o_ref.shape[0]):
        denom = jnp.maximum(l_scr[g], 1e-30)
        o_ref[g] = (acc_scr[g] / denom).astype(o_ref.dtype)


def _scratch(kv: int, rep: int, d: int):
    return [pltpu.VMEM((kv, rep, 1), jnp.float32),          # running max
            pltpu.VMEM((kv, rep, 1), jnp.float32),          # running denom
            pltpu.VMEM((kv, rep, d), jnp.float32)]          # accumulator


def _pad_caches(k_cache, v_cache):
    w = k_cache.shape[1]
    kt = kv_tile(w)
    pad_w = (-w) % kt
    if pad_w:
        widths = ((0, 0), (0, pad_w), (0, 0), (0, 0))
        k_cache = jnp.pad(k_cache, widths)
        v_cache = jnp.pad(v_cache, widths)
    return k_cache, v_cache, kt, (w + pad_w) // kt


def _kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, m_scr, l_scr, acc_scr, *,
            softcap: float, scale: float, kv_scale: float = 0.0):
    wi = pl.program_id(1)

    @pl.when(wi == 0)
    def _():
        _init(m_scr, l_scr, acc_scr)

    _update(q_ref, k_ref, v_ref, mask_ref[...] > 0, m_scr, l_scr, acc_scr,
            softcap=softcap, scale=scale, kv_scale=kv_scale)

    @pl.when(wi == pl.num_programs(1) - 1)
    def _():
        _finalize(o_ref, l_scr, acc_scr)


@functools.partial(jax.jit, static_argnames=("softcap", "kv_scale",
                                              "interpret"))
def flash_decode(q, k_cache, v_cache, kv_mask, *, softcap=0.0,
                 kv_scale=0.0, interpret=False):
    """q: (B,H,D); caches: (B,W,KV,D); kv_mask: (B,W) bool/int.

    ``kv_scale`` > 0 marks int8 caches quantised as round(x * kv_scale):
    dequantisation happens per block inside the kernel (VMEM), so cache
    HBM traffic is the int8 byte count.  Matches ref.decode_attention_ref
    on dequantised values.
    """
    b, h, d = q.shape
    w, kv = k_cache.shape[1], k_cache.shape[2]
    rep = h // kv
    kp, vp, kt, nw = _pad_caches(k_cache, v_cache)
    # (B, 1, W): the mask block's minor dims are (1, KT) — whole / aligned
    mp = jnp.pad(kv_mask.astype(jnp.int32), ((0, 0), (0, nw * kt - w)))
    mp = mp.reshape(b, 1, nw * kt)
    qg = q.reshape(b, kv, rep, d)

    kernel = functools.partial(_kernel, softcap=softcap,
                               scale=1.0 / (d ** 0.5), kv_scale=kv_scale)
    head_spec = pl.BlockSpec((None, kv, rep, d), lambda bi, wi: (bi, 0, 0, 0))
    cache_spec = pl.BlockSpec((None, kt, kv, d), lambda bi, wi: (bi, wi, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid=(b, nw),
        in_specs=[head_spec, cache_spec, cache_spec,
                  pl.BlockSpec((None, 1, kt), lambda bi, wi: (bi, 0, wi))],
        out_specs=head_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, rep, d), q.dtype),
        scratch_shapes=_scratch(kv, rep, d),
        interpret=interpret,
    )(qg, kp, vp, mp)
    return out.reshape(b, h, d)


def _pool_kernel(pos_ref, live_ref, q_ref, k_ref, v_ref, o_ref,
                 m_scr, l_scr, acc_scr, *, softcap: float, scale: float,
                 kv_scale: float, width: int, tile: int):
    bi = pl.program_id(0)
    wi = pl.program_id(1)

    @pl.when(wi == 0)
    def _():
        _init(m_scr, l_scr, acc_scr)

    # In-kernel validity: ring slots at depth <= this stream's pos (and
    # below the unpadded width), AND'd with the stream's slot-live bit.
    # Both scalars come from SMEM — no (B, W) mask ever hits HBM.
    kvpos = wi * tile + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    ok = jnp.logical_and(kvpos <= pos_ref[bi], kvpos < width)
    ok = jnp.logical_and(ok, live_ref[bi] > 0)               # (1, KT)
    _update(q_ref, k_ref, v_ref, ok, m_scr, l_scr, acc_scr,
            softcap=softcap, scale=scale, kv_scale=kv_scale)

    @pl.when(wi == pl.num_programs(1) - 1)
    def _():
        _finalize(o_ref, l_scr, acc_scr)


@functools.partial(jax.jit, static_argnames=("softcap", "kv_scale",
                                              "interpret"))
def pool_flash_decode(q, k_cache, v_cache, pos, live=None, *, softcap=0.0,
                      kv_scale=0.0, interpret=False):
    """Slot-pool decode attention: q (B,H,D); caches (B,W,KV,D);
    pos (B,) int32 per-stream ring positions; live (B,) optional
    slot-live mask (None = all live).

    A fully-dead row (live == 0) outputs zeros — its softmax
    normaliser never accumulates.  ``kv_scale`` as in ``flash_decode``.
    Matches ref.pool_decode_attention_ref up to float rounding.
    """
    b, h, d = q.shape
    w, kv = k_cache.shape[1], k_cache.shape[2]
    rep = h // kv
    kp, vp, kt, nw = _pad_caches(k_cache, v_cache)
    qg = q.reshape(b, kv, rep, d)
    pos1 = jnp.asarray(pos, jnp.int32).reshape(b)
    live1 = (jnp.ones((b,), jnp.int32) if live is None
             else (live > 0).astype(jnp.int32).reshape(b))

    kernel = functools.partial(_pool_kernel, softcap=softcap,
                               scale=1.0 / (d ** 0.5), kv_scale=kv_scale,
                               width=w, tile=kt)
    head_spec = pl.BlockSpec((None, kv, rep, d),
                             lambda bi, wi, *_: (bi, 0, 0, 0))
    cache_spec = pl.BlockSpec((None, kt, kv, d),
                              lambda bi, wi, *_: (bi, wi, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, nw),
            in_specs=[head_spec, cache_spec, cache_spec],
            out_specs=head_spec,
            scratch_shapes=_scratch(kv, rep, d)),
        out_shape=jax.ShapeDtypeStruct((b, kv, rep, d), q.dtype),
        interpret=interpret,
    )(pos1, live1, qg, kp, vp)
    return out.reshape(b, h, d)
