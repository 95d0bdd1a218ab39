"""Attention blocks: MHA / GQA / MQA with RoPE, qk-norm, sliding window,
prefix-LM and encoder-only (bidirectional) variants, plus KV-cache decode.

Covers the attention flavours of every assigned architecture:
  h2o-danube (GQA kv=8 + SWA), qwen3 (GQA + qk_norm), stablelm (partial
  rotary), phi4 (GQA kv=8), paligemma (MQA kv=1, prefix-LM), grok
  (logit soft-capping), hubert (bidirectional, no cache), zamba2 (shared
  block), qwen3-moe.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models import layers
from repro.models.config import ModelConfig
from repro.models.partitioning import shard


def attention_axes(cfg: ModelConfig) -> dict:
    ax = {
        "wq": ("fsdp", "heads", "head_dim"),
        "wk": ("fsdp", "kv_heads", "head_dim"),
        "wv": ("fsdp", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "fsdp"),
    }
    if cfg.qk_norm:
        ax["q_norm"] = ("head_dim",)
        ax["k_norm"] = ("head_dim",)
    return ax


def init_attention(cfg: ModelConfig, rng, dtype) -> dict:
    rngs = jax.random.split(rng, 4)
    h, kv, hd, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_model
    p = {
        "wq": layers.trunc_normal(rngs[0], (d, h, hd), d ** -0.5, dtype),
        "wk": layers.trunc_normal(rngs[1], (d, kv, hd), d ** -0.5, dtype),
        "wv": layers.trunc_normal(rngs[2], (d, kv, hd), d ** -0.5, dtype),
        "wo": layers.trunc_normal(rngs[3], (h, hd, d),
                                  (h * hd) ** -0.5 / (2 * cfg.num_layers) ** 0.5,
                                  dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _qkv(cfg: ModelConfig, p: dict, x: jnp.ndarray, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = layers.rms_norm_head(q, p["q_norm"], cfg.norm_eps)
        k = layers.rms_norm_head(k, p["k_norm"], cfg.norm_eps)
    q = layers.apply_rope(cfg, q, positions)
    k = layers.apply_rope(cfg, k, positions)
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", "kv_heads", None)
    v = shard(v, "batch", "seq", "kv_heads", None)
    return q, k, v


def attention_block(cfg: ModelConfig, p: dict, x: jnp.ndarray,
                    positions: jnp.ndarray) -> jnp.ndarray:
    """Full-sequence attention (training / prefill without cache return)."""
    q, k, v = _qkv(cfg, p, x, positions)
    out = ops.attention(
        q, k, v, causal=cfg.causal, window=cfg.sliding_window,
        prefix=cfg.num_patches if cfg.prefix_lm else 0,
        softcap=cfg.attn_logit_softcap, unroll=cfg.unroll_scans)
    out = shard(out, "batch", "seq", "heads", None)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


# --------------------------------------------------------------- KV caching

def cache_width(cfg: ModelConfig, max_len: int) -> int:
    """Ring-buffer width: the SWA window bounds the live KV footprint."""
    if cfg.sliding_window is not None:
        return min(max_len, cfg.sliding_window)
    return max_len


INT8_KV_SCALE = 32.0   # static symmetric scale; logit error < 1% for
                       # unit-RMS keys (validated in tests/test_archs)


def _kv_store_dtype(cfg: ModelConfig, dtype):
    return jnp.int8 if cfg.kv_cache_dtype == "int8" else dtype


def quantize_kv(cfg: ModelConfig, x: jnp.ndarray, store_dtype) -> jnp.ndarray:
    if store_dtype == jnp.int8:
        return jnp.clip(jnp.round(x.astype(jnp.float32) * INT8_KV_SCALE),
                        -127, 127).astype(jnp.int8)
    return x.astype(store_dtype)


def dequantize_kv(cfg: ModelConfig, x: jnp.ndarray, compute_dtype):
    if x.dtype == jnp.int8:
        return (x.astype(jnp.float32) / INT8_KV_SCALE).astype(compute_dtype)
    return x


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  dtype) -> dict:
    w = cache_width(cfg, max_len)
    shape = (batch, w, cfg.num_kv_heads, cfg.head_dim)
    store = _kv_store_dtype(cfg, dtype)
    return {"k": jnp.zeros(shape, store), "v": jnp.zeros(shape, store)}


def kv_cache_axes() -> dict:
    # "kv_seq" is separately mappable: when kv_heads doesn't divide the
    # model axis (GQA kv=1..8 on 16-way TP) the launcher shards the cache
    # length instead (flash-decode style cache-split, DESIGN.md §7).
    return {"k": ("batch", "kv_seq", "kv_heads", "head_dim"),
            "v": ("batch", "kv_seq", "kv_heads", "head_dim")}


def attention_prefill(cfg: ModelConfig, p: dict, x: jnp.ndarray,
                      positions: jnp.ndarray, cache: dict,
                      ) -> Tuple[jnp.ndarray, dict]:
    """Prefill: full attention AND populate the (ring) KV cache.

    For SWA models only the last ``window`` keys are retained.
    """
    q, k, v = _qkv(cfg, p, x, positions)
    out = ops.attention(
        q, k, v, causal=cfg.causal, window=cfg.sliding_window,
        prefix=cfg.num_patches if cfg.prefix_lm else 0,
        softcap=cfg.attn_logit_softcap, unroll=cfg.unroll_scans)
    w = cache["k"].shape[1]
    s = k.shape[1]
    kq = quantize_kv(cfg, k, cache["k"].dtype)
    vq = quantize_kv(cfg, v, cache["v"].dtype)
    if s >= w:
        # Keep the trailing window; ring order: slot = pos % w.
        tail_k, tail_v = kq[:, s - w:], vq[:, s - w:]
        pos_tail = (jnp.arange(s - w, s) % w)
        new_k = jnp.zeros_like(cache["k"]).at[:, pos_tail].set(tail_k)
        new_v = jnp.zeros_like(cache["v"]).at[:, pos_tail].set(tail_v)
    else:
        new_k = jax.lax.dynamic_update_slice(
            cache["k"], kq, (0, 0, 0, 0))
        new_v = jax.lax.dynamic_update_slice(
            cache["v"], vq, (0, 0, 0, 0))
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return out, {"k": new_k, "v": new_v}


def attention_decode(cfg: ModelConfig, p: dict, x: jnp.ndarray,
                     pos: jnp.ndarray, cache: dict, i,
                     live: Optional[jnp.ndarray] = None
                     ) -> Tuple[jnp.ndarray, dict]:
    """One-token decode at layer ``i`` of a run's stacked cache: x (B, 1,
    d), cache leaves (L, B, W, KV, D), pos scalar int32 (shared position)
    or (B,) int32 per-stream positions (slot-pool continuous batching,
    DESIGN.md §10 — streams admitted at different rounds sit at
    different cache depths).

    Writes the new KV row in place into the stacked cache, at
    ``(i, :, pos % width)`` (scalar pos) or ``(i, rows, pos % width)``
    (per-stream pos), attends over layer ``i``'s valid slots and returns
    the whole stacked cache.  The per-stream branch never materialises a
    (B, W) validity mask: it hands the position vector (and the optional
    (B,) ``live`` slot mask of the coded pool) to
    ``ops.pool_decode_attention``, which derives tile validity in-kernel
    on the Pallas path.  ``live`` is ignored in the scalar-pos branch
    (one shared depth has no dead slots).
    """
    pos = jnp.asarray(pos, jnp.int32)
    w = cache["k"].shape[2]
    kv_scale = (INT8_KV_SCALE if cache["k"].dtype == jnp.int8 else 0.0)
    if pos.ndim == 0:
        q, k, v = _qkv(cfg, p, x, pos[None])
        slot = jnp.mod(pos, w)
        new_k = jax.lax.dynamic_update_slice(
            cache["k"], quantize_kv(cfg, k, cache["k"].dtype)[None],
            (i, 0, slot, 0, 0))
        new_v = jax.lax.dynamic_update_slice(
            cache["v"], quantize_kv(cfg, v, cache["v"].dtype)[None],
            (i, 0, slot, 0, 0))
        valid = jnp.arange(w)[None, :] <= pos             # (1, W) -> (B, W)
        valid = jnp.broadcast_to(valid, (x.shape[0], w))
        out = ops.decode_attention(q[:, 0], new_k[i], new_v[i], valid,
                                   softcap=cfg.attn_logit_softcap,
                                   kv_scale=kv_scale)
    else:
        # Per-stream ring slots: a batched scatter replaces the shared
        # dynamic_update_slice (each stream writes at its own depth,
        # O(B) traffic — not a full-cache select).
        q, k, v = _qkv(cfg, p, x, pos[:, None])
        rows = jnp.arange(x.shape[0])
        slot = jnp.mod(pos, w)
        new_k = cache["k"].at[i, rows, slot].set(
            quantize_kv(cfg, k, cache["k"].dtype)[:, 0])
        new_v = cache["v"].at[i, rows, slot].set(
            quantize_kv(cfg, v, cache["v"].dtype)[:, 0])
        out = ops.pool_decode_attention(q[:, 0], new_k[i], new_v[i], pos,
                                        live=live,
                                        softcap=cfg.attn_logit_softcap,
                                        kv_scale=kv_scale)
    out = jnp.einsum("bhk,hkd->bd", out, p["wo"])[:, None]
    return out, {"k": new_k, "v": new_v}
