"""Pallas TPU kernel: blocked flash attention (prefill / training).

Online-softmax attention tiled up to 128x128 with causal, sliding-window,
prefix-LM and logit-softcap support — the prefill_32k hot spot.  GQA is
handled in-kernel (q-head h reads kv-head h // rep), so no KV repetition
is materialised.

TPU notes: Mosaic requires the last two dims of every block to be
(8, 128)-aligned or whole, so q/k/v blocks carry the WHOLE head axis —
``(tile, heads, head_dim)`` — and the kernel loops over heads with static
indices; a K/V tile streams HBM->VMEM once per q-tile for every head that
reads it.  The grid's last axis (KV tiles) is innermost-sequential, so
per-head fp32 running max / sum / accumulator live in VMEM scratch across
KV iterations.  Fully-masked KV tiles (outside the causal wedge or SWA
band) are skipped with pl.when — for SWA the skipped fraction approaches
1 - window/S.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
Q_TILE = 128
KV_TILE = 128


def _tile(n: int, cap: int) -> int:
    """Sequence tile: ``cap``, or ``n`` rounded up to 8 rows when shorter
    (a 16-token prompt is not padded to a 128-row tile)."""
    return min(cap, -(-n // 8) * 8)


def _nt_dot(a, b):
    """a @ b.T with fp32 accumulation (contract the last dims)."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            causal: bool, window: Optional[int], prefix: int,
            softcap: float, scale: float, kv_len: int, q_offset: int,
            q_tile: int, kv_tile: int, rep: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    num_kv = k_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * q_tile + q_offset
    k_start = ki * kv_tile

    # tile-level visibility test (skip fully-masked tiles)
    visible = True
    if causal:
        visible = k_start <= q_start + q_tile - 1
        if prefix > 0:
            visible = jnp.logical_or(visible, k_start < prefix)
    if window is not None:
        visible = jnp.logical_and(
            visible, k_start + kv_tile - 1 > q_start - window)

    @pl.when(visible)
    def _compute():
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (q_tile, kv_tile), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (q_tile, kv_tile), 1)
        ok = kpos < kv_len
        if causal:
            allowed = kpos <= qpos
            if prefix > 0:
                allowed = jnp.logical_or(allowed, kpos < prefix)
            ok = jnp.logical_and(ok, allowed)
        if window is not None:
            ok = jnp.logical_and(ok, kpos > qpos - window)
        for g in range(num_kv):
            k = k_ref[:, g, :].astype(jnp.float32)           # (KT, D)
            v = v_ref[:, g, :].astype(jnp.float32)           # (KT, D)
            for h in range(g * rep, (g + 1) * rep):
                q = q_ref[:, h, :].astype(jnp.float32) * scale   # (QT, D)
                s = _nt_dot(q, k)
                if softcap > 0.0:
                    s = softcap * jnp.tanh(s / softcap)
                s = jnp.where(ok, s, NEG_INF)

                m_prev = m_scr[h]                            # (QT, 1)
                m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
                # guard fully-masked rows (exp of NEG_INF - NEG_INF)
                m_safe = jnp.where(m_new <= NEG_INF, 0.0, m_new)
                p = jnp.exp(s - m_safe)
                p = jnp.where(ok, p, 0.0)
                alpha = jnp.where(m_prev <= NEG_INF, 0.0,
                                  jnp.exp(m_prev - m_safe))
                l_scr[h] = l_scr[h] * alpha + jnp.sum(p, -1, keepdims=True)
                acc_scr[h] = acc_scr[h] * alpha + jnp.dot(
                    p, v, preferred_element_type=jnp.float32)
                m_scr[h] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        for h in range(o_ref.shape[1]):
            denom = jnp.maximum(l_scr[h], 1e-30)
            o_ref[:, h, :] = (acc_scr[h] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "prefix", "softcap",
                              "q_offset", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=None, prefix=0,
                    softcap=0.0, q_offset=0, interpret=False):
    """q: (B,S,H,D); k,v: (B,L,KV,D).  Matches ref.attention_ref."""
    b, s, h, d = q.shape
    l, kv = k.shape[1], k.shape[2]
    rep = h // kv
    scale = 1.0 / (d ** 0.5)

    qt, kt = _tile(s, Q_TILE), _tile(l, KV_TILE)
    pad_q = (-s) % qt
    pad_k = (-l) % kt
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    sq, sk = s + pad_q, l + pad_k

    grid = (b, sq // qt, sk // kt)
    kernel = functools.partial(
        _kernel, causal=causal, window=window, prefix=prefix,
        softcap=softcap, scale=scale, kv_len=l, q_offset=q_offset,
        q_tile=qt, kv_tile=kt, rep=rep)
    q_spec = pl.BlockSpec((None, qt, h, d), lambda bi, qi, ki: (bi, qi, 0, 0))
    kv_spec = pl.BlockSpec((None, kt, kv, d),
                           lambda bi, qi, ki: (bi, ki, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, sq, h, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((h, qt, 1), jnp.float32),      # running max
            pltpu.VMEM((h, qt, 1), jnp.float32),      # running denom
            pltpu.VMEM((h, qt, d), jnp.float32),      # output accumulator
        ],
        interpret=interpret,
    )(qp, kp, vp)
    if pad_q:
        out = out[:, :s]
    return out
