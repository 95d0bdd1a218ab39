"""Pure-jnp reference oracles for every Pallas kernel.

These are the ground truth the kernels are validated against
(tests/test_kernels.py sweeps shapes/dtypes with assert_allclose), and the
implementation used on non-TPU backends (ops.py dispatch).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# ------------------------------------------------------------- berrut matmul

def berrut_apply_ref(weights: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Coded encode/decode contraction: (O, I) @ (..., I, F) -> (..., O, F).

    The ApproxIFER hot path: every query group passes through this with
    O = N+1 (encode) or O = K (decode) and F = the flattened feature dim.
    """
    return jnp.einsum("oi,...if->...of", weights.astype(jnp.float32),
                      x.astype(jnp.float32)).astype(x.dtype)


def berrut_encode_dispatch_ref(weights: jnp.ndarray,
                               x: jnp.ndarray) -> jnp.ndarray:
    """Oracle for ``berrut_matmul.berrut_encode_dispatch``.

    Encode + worker-major stream layout in one definition: the (O, I)
    Berrut contraction over (G, I, F) followed by the flat ``n*G + g``
    stream ordering the "worker" mesh axis shards (DESIGN.md §13).
    Composing ``berrut_apply_ref`` with the swapaxes/reshape keeps this
    byte-identical to the pre-fused two-pass path — the layout move is
    free here (XLA relayouts on the copy) while the kernel writes each
    output tile straight into the worker-major block.
    """
    coded = berrut_apply_ref(weights, x)                  # (G, O, F)
    return jnp.swapaxes(coded, 0, 1).reshape(-1, x.shape[-1])


def fused_group_decode_ref(grouped: jnp.ndarray, masks: jnp.ndarray,
                           alphas: jnp.ndarray, betas: jnp.ndarray, *,
                           c_vote: int = 0):
    """Oracle for ``berrut_decode.fused_group_decode``.

    (G, N+1, V) coded block + masks -> (G, K, V) decoded logits via the
    canonical ``core.berrut`` survivor-weight matrix construction, plus
    (with ``c_vote > 0``) the (G, N+1, C) float32 vote-coordinate gather
    — read from the raw block BEFORE the float32 upcast (a locate-only
    caller never forces a full-precision copy; the decode's f32 convert
    exists only to feed its own contraction).

    masks: (N+1,) shared availability (one decode matrix for every
    group) or (G, N+1) per-group exclusion masks.
    """
    from repro.core import berrut
    from repro.core.error_locator import gather_vote_values

    def matrix(m):
        return berrut.basis_matrix(alphas, betas,
                                   berrut.survivor_weights(m), mask=m)

    # one convert feeding the batched matmul (the contraction needs the
    # f32 operand materialised either way; converting inside the vmap
    # makes XLA CPU stage it per group, measurably slower at bf16)
    grouped32 = grouped.astype(jnp.float32)

    def contract(w, x):
        return jnp.dot(w, x, preferred_element_type=jnp.float32)

    if masks.ndim == 1:
        # One shared mask: broadcast the MASK, not the matrix, and take
        # the same per-group batched path.  Rebuilding the (tiny) matrix
        # per group is free next to the (N+1, V) contraction, while both
        # a plain (K, N+1) @ (G, N+1, V) free-dim contraction and a
        # broadcast-matrix batched dot make XLA pick slow layouts
        # (transpose of the full output block / degenerate batch
        # strides) — measured up to ~7x slower at V = 32k.
        masks = jnp.broadcast_to(masks, (grouped.shape[0],
                                         masks.shape[0]))
    decoded = jax.vmap(
        lambda m, x: contract(matrix(m), x))(masks, grouped32)
    decoded = decoded.astype(grouped.dtype)
    if c_vote <= 0:
        return decoded
    return decoded, gather_vote_values(grouped, c_vote)


# ---------------------------------------------------------------- attention

def _mask_bias(q_len: int, kv_len: int, *, causal: bool,
               window: Optional[int], prefix: int,
               q_offset: int = 0) -> jnp.ndarray:
    """(q_len, kv_len) additive bias encoding causal/SWA/prefix-LM rules."""
    qpos = jnp.arange(q_len)[:, None] + q_offset
    kpos = jnp.arange(kv_len)[None, :]
    allowed = jnp.ones((q_len, kv_len), bool)
    if causal:
        allowed = kpos <= qpos
        if prefix > 0:  # prefix-LM: bidirectional over the first ``prefix``
            allowed = jnp.logical_or(allowed, kpos < prefix)
    if window is not None:
        allowed = jnp.logical_and(allowed, kpos > qpos - window)
    return jnp.where(allowed, 0.0, NEG_INF)


def attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                  causal: bool = True, window: Optional[int] = None,
                  prefix: int = 0, softcap: float = 0.0,
                  q_offset: int = 0) -> jnp.ndarray:
    """Full (prefill/train) attention with GQA.

    q: (B, S, H, D); k, v: (B, L, KV, D) with H % KV == 0.
    """
    b, s, h, d = q.shape
    kv = k.shape[2]
    rep = h // kv
    qf = q.astype(jnp.float32) / jnp.sqrt(d).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    qg = qf.reshape(b, s, kv, rep, d)
    scores = jnp.einsum("bsgrd,blgd->bgrsl", qg, kf)
    if softcap > 0.0:
        scores = softcap * jnp.tanh(scores / softcap)
    bias = _mask_bias(s, k.shape[1], causal=causal, window=window,
                      prefix=prefix, q_offset=q_offset)
    scores = scores + bias[None, None, None]
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgrsl,blgd->bsgrd", probs, vf)
    return out.reshape(b, s, h, d).astype(q.dtype)


def decode_attention_ref(q: jnp.ndarray, k_cache: jnp.ndarray,
                         v_cache: jnp.ndarray, kv_mask: jnp.ndarray, *,
                         softcap: float = 0.0) -> jnp.ndarray:
    """Single-token decode attention against a (ring-buffer) KV cache.

    q: (B, H, D); caches: (B, W, KV, D); kv_mask: (B, W) validity.
    """
    b, h, d = q.shape
    kv = k_cache.shape[2]
    rep = h // kv
    qf = q.astype(jnp.float32) / jnp.sqrt(d).astype(jnp.float32)
    qg = qf.reshape(b, kv, rep, d)
    scores = jnp.einsum("bgrd,bwgd->bgrw", qg, k_cache.astype(jnp.float32))
    if softcap > 0.0:
        scores = softcap * jnp.tanh(scores / softcap)
    scores = jnp.where(kv_mask[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgrw,bwgd->bgrd", probs, v_cache.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)


def pool_decode_attention_ref(q: jnp.ndarray, k_cache: jnp.ndarray,
                              v_cache: jnp.ndarray, pos: jnp.ndarray,
                              live: Optional[jnp.ndarray] = None, *,
                              softcap: float = 0.0,
                              kv_scale: float = 0.0,
                              block: int = 512) -> jnp.ndarray:
    """Oracle for ``flash_decode.pool_flash_decode`` (slot-pool decode).

    Blocked online-softmax in the kernel's op order — same tile width
    (``block``, or the whole ring when shorter), same masked-exp/rescale
    sequence, same ``acc / max(l, 1e-30)`` finalisation.  The two are
    separate programs, so they agree to float rounding, not bitwise.  The mask
    is never materialised at (B, W): each tile derives validity from the
    per-stream ``pos`` ring positions (``kvpos <= pos`` — the live
    ring-buffer slots of DESIGN.md §10) composed with the optional
    per-stream ``live`` slot mask.  A fully-dead row (live == 0) returns
    zeros (l stays 0), unlike ``decode_attention_ref``'s uniform-softmax
    garbage on an all-false mask row.

    q: (B, H, D); caches: (B, W, KV, D); pos: (B,) int32; live: (B,).
    ``kv_scale`` > 0 dequantises int8 caches per tile, as the kernel does.
    """
    b, h, d = q.shape
    w, kv = k_cache.shape[1], k_cache.shape[2]
    rep = h // kv
    scale = 1.0 / (d ** 0.5)
    block = min(block, w)
    pad_w = (-w) % block
    kp = jnp.pad(k_cache, ((0, 0), (0, pad_w), (0, 0), (0, 0)))
    vp = jnp.pad(v_cache, ((0, 0), (0, pad_w), (0, 0), (0, 0)))
    nb = (w + pad_w) // block
    qg = q.reshape(b, kv, rep, d).astype(jnp.float32) * scale
    pos = jnp.asarray(pos, jnp.int32)
    kb = jnp.moveaxis(kp.reshape(b, nb, block, kv, d), 1, 0)
    vb = jnp.moveaxis(vp.reshape(b, nb, block, kv, d), 1, 0)

    def body(carry, xs):
        m_prev, l_prev, acc = carry
        k_blk, v_blk, bi = xs
        kf = k_blk.astype(jnp.float32)
        vf = v_blk.astype(jnp.float32)
        if kv_scale > 0.0:
            kf = kf / kv_scale
            vf = vf / kv_scale
        s = jnp.einsum("bgrd,btgd->bgrt", qg, kf)
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        kvpos = bi * block + jnp.arange(block)[None, :]   # (1, T)
        ok = jnp.logical_and(kvpos <= pos[:, None], kvpos < w)
        if live is not None:
            ok = jnp.logical_and(ok, (live > 0)[:, None])
        okb = ok[:, None, None, :]                        # (B,1,1,T)
        s = jnp.where(okb, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        m_safe = jnp.where(m_new <= NEG_INF, 0.0, m_new)
        p = jnp.exp(s - m_safe)
        p = jnp.where(okb, p, 0.0)
        alpha = jnp.where(m_prev <= NEG_INF, 0.0, jnp.exp(m_prev - m_safe))
        l_new = l_prev * alpha + jnp.sum(p, -1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bgrt,btgd->bgrd", p, vf)
        return (m_new, l_new, acc), None

    init = (jnp.full((b, kv, rep, 1), NEG_INF, jnp.float32),
            jnp.zeros((b, kv, rep, 1), jnp.float32),
            jnp.zeros((b, kv, rep, d), jnp.float32))
    (_, lsum, acc), _ = jax.lax.scan(body, init, (kb, vb, jnp.arange(nb)))
    out = acc / jnp.maximum(lsum, 1e-30)
    return out.reshape(b, h, d).astype(q.dtype)


def attention_blocked(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      causal: bool = True, window: Optional[int] = None,
                      prefix: int = 0, softcap: float = 0.0,
                      q_offset: int = 0, block: int = 1024,
                      unroll: bool = False) -> jnp.ndarray:
    """Flash-style blocked attention in pure XLA (no Pallas).

    Online-softmax scan over KV blocks: peak materialised score memory is
    S x block instead of S x L — the §Perf optimisation that removes the
    prefill_32k memory blow-up on the jnp path (the Pallas kernel does the
    same thing in VMEM on real TPUs).  Matches attention_ref.
    """
    b, s, h, d = q.shape
    l, kv = k.shape[1], k.shape[2]
    rep = h // kv
    blk = min(block, l)
    pad = (-l) % blk
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nb = (l + pad) // blk

    qf = (q.astype(jnp.float32) / jnp.sqrt(d).astype(jnp.float32))
    qg = qf.reshape(b, s, kv, rep, d)
    qpos = (jnp.arange(s) + q_offset)[:, None]            # (S, 1)

    kb = jnp.moveaxis(kp.reshape(b, nb, blk, kv, d), 1, 0)
    vb = jnp.moveaxis(vp.reshape(b, nb, blk, kv, d), 1, 0)

    def body(carry, xs):
        m_prev, l_prev, acc = carry
        k_blk, v_blk, bi = xs
        scores = jnp.einsum("bsgrd,blgd->bgrsl", qg,
                            k_blk.astype(jnp.float32))
        if softcap > 0.0:
            scores = softcap * jnp.tanh(scores / softcap)
        kpos = bi * blk + jnp.arange(blk)[None, :]        # (1, blk)
        ok = (kpos < l) * jnp.ones((s, 1), bool)
        if causal:
            allowed = kpos <= qpos
            if prefix > 0:
                allowed = jnp.logical_or(allowed, kpos < prefix)
            ok = jnp.logical_and(ok, allowed)
        if window is not None:
            ok = jnp.logical_and(ok, kpos > qpos - window)
        scores = jnp.where(ok[None, None, None], scores, NEG_INF)

        m_new = jnp.maximum(m_prev, jnp.max(scores, -1))
        m_safe = jnp.where(m_new <= NEG_INF, 0.0, m_new)
        p = jnp.exp(scores - m_safe[..., None])
        p = jnp.where(ok[None, None, None], p, 0.0)
        alpha = jnp.where(m_prev <= NEG_INF, 0.0,
                          jnp.exp(m_prev - m_safe))
        l_new = l_prev * alpha + jnp.sum(p, -1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bgrsl,blgd->bgrsd", p, v_blk.astype(jnp.float32))
        return (m_new, l_new, acc), None

    init = (jnp.full((b, kv, rep, s), NEG_INF, jnp.float32),
            jnp.zeros((b, kv, rep, s), jnp.float32),
            jnp.zeros((b, kv, rep, s, d), jnp.float32))
    (m, lsum, acc), _ = jax.lax.scan(
        body, init, (kb, vb, jnp.arange(nb)),
        unroll=True if unroll else 1)
    out = acc / jnp.maximum(lsum, 1e-30)[..., None]
    out = jnp.moveaxis(out, 3, 1).reshape(b, s, h, d)
    return out.astype(q.dtype)


# ---------------------------------------------------------------- Mamba2 SSD

def ssd_scan_ref(x: jnp.ndarray, dt: jnp.ndarray, a_log: jnp.ndarray,
                 b: jnp.ndarray, c: jnp.ndarray, d_skip: jnp.ndarray,
                 h0: Optional[jnp.ndarray] = None):
    """Sequential (exact) SSD recurrence — the oracle for the chunked kernel.

    x:  (B, S, H, P)   inputs per head
    dt: (B, S, H)      softplus'd step sizes
    a_log: (H,)        log of -A (A = -exp(a_log))
    b, c: (B, S, N)    input/output projections (single group, broadcast
                       over heads as in Mamba2's default G=1)
    d_skip: (H,)       skip connection
    h0: (B, H, P, N)   initial state
    Returns (y (B,S,H,P), h_final (B,H,P,N)).
    """
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    decay = jnp.exp(-jnp.exp(a_log.astype(jnp.float32))[None, None, :]
                    * dt.astype(jnp.float32))            # (B,S,H)
    xbar = x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None]
    if h0 is None:
        h0 = jnp.zeros((bsz, h, p, n), jnp.float32)

    def step(hprev, t):
        dec_t, xb_t, b_t, c_t = t
        hnew = hprev * dec_t[:, :, None, None] + jnp.einsum(
            "bhp,bn->bhpn", xb_t, b_t)
        y_t = jnp.einsum("bhpn,bn->bhp", hnew, c_t)
        return hnew, y_t

    xs = (jnp.moveaxis(decay, 1, 0), jnp.moveaxis(xbar, 1, 0),
          jnp.moveaxis(b.astype(jnp.float32), 1, 0),
          jnp.moveaxis(c.astype(jnp.float32), 1, 0))
    h_final, ys = jax.lax.scan(step, h0.astype(jnp.float32), xs)
    y = jnp.moveaxis(ys, 0, 1) + x.astype(jnp.float32) * d_skip[None, None, :, None]
    return y.astype(x.dtype), h_final


def ssd_chunked_ref(x, dt, a_log, b, c, d_skip, h0=None, chunk: int = 128):
    """Chunked (matmul-form) SSD — the state-space-duality algorithm the
    Pallas kernel implements; validated against ssd_scan_ref."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    q = min(chunk, s)
    if s % q:
        raise ValueError(f"seq {s} not divisible by chunk {q}")
    nc = s // q
    dtf = dt.astype(jnp.float32)
    la = -jnp.exp(a_log.astype(jnp.float32))[None, None, :] * dtf  # log decay
    xbar = x.astype(jnp.float32) * dtf[..., None]

    la_c = la.reshape(bsz, nc, q, h)
    xb_c = xbar.reshape(bsz, nc, q, h, p)
    b_c = b.astype(jnp.float32).reshape(bsz, nc, q, n)
    c_c = c.astype(jnp.float32).reshape(bsz, nc, q, n)

    lcum = jnp.cumsum(la_c, axis=2)                      # (B,NC,Q,H)
    ltot = lcum[:, :, -1]                                # (B,NC,H)

    # intra-chunk: att[t, tau] = (c_t . b_tau) exp(L_t - L_tau), tau <= t
    gap = lcum[:, :, :, None, :] - lcum[:, :, None, :, :]   # (B,NC,Q,Q,H)
    tri = jnp.tril(jnp.ones((q, q), bool))
    att = jnp.einsum("bcqn,bctn->bcqt", c_c, b_c)[..., None] \
        * jnp.exp(jnp.where(tri[None, None, :, :, None], gap, NEG_INF))
    y_intra = jnp.einsum("bcqth,bcthp->bcqhp", att, xb_c)

    # chunk summary states and inter-chunk recurrence
    decay_to_end = jnp.exp(ltot[:, :, None, :] - lcum)   # (B,NC,Q,H)
    s_chunk = jnp.einsum("bcqn,bcqh,bcqhp->bchpn", b_c, decay_to_end, xb_c)
    if h0 is None:
        h0 = jnp.zeros((bsz, h, p, n), jnp.float32)

    def carry(hprev, t):
        ltot_c, s_c = t
        hout = hprev * jnp.exp(ltot_c)[:, :, None, None] + s_c
        return hout, hprev

    (h_final, h_ins) = jax.lax.scan(
        carry, h0.astype(jnp.float32),
        (jnp.moveaxis(ltot, 1, 0), jnp.moveaxis(s_chunk, 1, 0)))
    h_ins = jnp.moveaxis(h_ins, 0, 1)                    # (B,NC,H,P,N)

    y_inter = jnp.einsum("bcqn,bchpn->bcqhp", c_c, h_ins) \
        * jnp.exp(lcum)[..., None]
    y = (y_intra + y_inter).reshape(bsz, s, h, p) \
        + x.astype(jnp.float32) * d_skip[None, None, :, None]
    return y.astype(x.dtype), h_final


def ssd_step_ref(h, x_t, dt_t, a_log, b_t, c_t, d_skip):
    """Single-token SSD decode step.

    h: (B,H,P,N), x_t: (B,H,P), dt_t: (B,H), b_t/c_t: (B,N).
    Returns (y_t (B,H,P), h_new).
    """
    decay = jnp.exp(-jnp.exp(a_log.astype(jnp.float32))[None, :]
                    * dt_t.astype(jnp.float32))          # (B,H)
    xb = x_t.astype(jnp.float32) * dt_t.astype(jnp.float32)[..., None]
    h_new = h * decay[:, :, None, None] + jnp.einsum("bhp,bn->bhpn", xb,
                                                     b_t.astype(jnp.float32))
    y = jnp.einsum("bhpn,bn->bhp", h_new, c_t.astype(jnp.float32)) \
        + x_t.astype(jnp.float32) * d_skip[None, :, None]
    return y.astype(x_t.dtype), h_new
