"""Block composition: per-layer kinds, stacked-parameter runs, scan.

The layer pattern (config.layer_pattern) is split into *runs* of identical
block kinds; each run's parameters are stacked on a leading "layers" axis
and applied with ``jax.lax.scan`` — one traced block per run keeps XLA
compile times sane for 64-layer models on the 512-device dry-run mesh.

Kinds:  "A" attention+MLP   "M" attention+MoE   "S" Mamba2 (SSD)
        "G" zamba2's shared-weight attention block (one param set reused
            at every G position; per-position KV caches).
"""

from __future__ import annotations

from typing import Any, List, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention, layers, mamba2, mlp, moe
from repro.models.config import ModelConfig


def pattern_runs(pattern: str) -> List[Tuple[str, int]]:
    runs: List[Tuple[str, int]] = []
    for kind in pattern:
        if runs and runs[-1][0] == kind and kind != "G":
            runs[-1] = (kind, runs[-1][1] + 1)
        else:
            runs.append((kind, 1))
    return runs


# ------------------------------------------------------------- per-block init

def _block_axes(cfg: ModelConfig, kind: str) -> dict:
    if kind == "S":
        return {"norm": layers.norm_axes(cfg),
                "ssm": mamba2.mamba2_axes(cfg)}
    ax = {"norm1": layers.norm_axes(cfg),
          "attn": attention.attention_axes(cfg),
          "norm2": layers.norm_axes(cfg)}
    ax["moe" if kind == "M" else "mlp"] = (
        moe.moe_axes(cfg) if kind == "M" else mlp.mlp_axes(cfg))
    return ax


def _init_block(cfg: ModelConfig, kind: str, rng, dtype) -> dict:
    r1, r2 = jax.random.split(rng)
    if kind == "S":
        return {"norm": layers.init_norm(cfg, dtype),
                "ssm": mamba2.init_mamba2(cfg, r1, dtype)}
    p = {"norm1": layers.init_norm(cfg, dtype),
         "attn": attention.init_attention(cfg, r1, dtype),
         "norm2": layers.init_norm(cfg, dtype)}
    p["moe" if kind == "M" else "mlp"] = (
        moe.init_moe(cfg, r2, dtype) if kind == "M"
        else mlp.init_mlp(cfg, r2, dtype))
    return p


def _stack(trees: List[Any]):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def init_blocks(cfg: ModelConfig, rng, dtype) -> dict:
    """Returns {"runs": [stacked-or-single params per run], "shared": ...}."""
    runs = pattern_runs(cfg.layer_pattern)
    out: dict = {"runs": []}
    rngs = jax.random.split(rng, len(runs) + 1)
    for (kind, count), r in zip(runs, rngs[:-1]):
        if kind == "G":
            out["runs"].append({})      # weights live in out["shared"]
            continue
        layer_rngs = jax.random.split(r, count)
        out["runs"].append(_stack(
            [_init_block(cfg, kind, lr, dtype) for lr in layer_rngs]))
    if "G" in cfg.layer_pattern:
        out["shared"] = _init_block(cfg, "A", rngs[-1], dtype)
    return out


def blocks_axes(cfg: ModelConfig) -> dict:
    runs = pattern_runs(cfg.layer_pattern)
    out: dict = {"runs": []}
    for kind, count in runs:
        if kind == "G":
            out["runs"].append({})
            continue
        ax = _block_axes(cfg, kind)
        # stacked leading layer axis
        out["runs"].append(jax.tree.map(
            lambda t: ("layers",) + t, ax,
            is_leaf=lambda x: isinstance(x, tuple)))
    if "G" in cfg.layer_pattern:
        out["shared"] = _block_axes(cfg, "A")
    return out


# ------------------------------------------------------------- cache init

def init_run_caches(cfg: ModelConfig, batch: int, max_len: int,
                    dtype) -> list:
    """One cache pytree per run (stacked on the run's layer axis)."""
    caches = []
    for kind, count in pattern_runs(cfg.layer_pattern):
        if kind == "S":
            one = mamba2.init_ssm_cache(cfg, batch, dtype)
        else:
            one = attention.init_kv_cache(cfg, batch, max_len, dtype)
        caches.append(jax.tree.map(
            lambda x: jnp.broadcast_to(x, (count,) + x.shape), one))
    return caches


def run_cache_axes(cfg: ModelConfig) -> list:
    axes = []
    for kind, _ in pattern_runs(cfg.layer_pattern):
        one = (mamba2.ssm_cache_axes() if kind == "S"
               else attention.kv_cache_axes())
        axes.append(jax.tree.map(lambda t: ("layers",) + t, one,
                                 is_leaf=lambda x: isinstance(x, tuple)))
    return axes


# ------------------------------------------------------------- block apply

def _empty_aux():
    return {"load_balance_loss": jnp.zeros((), jnp.float32),
            "router_z_loss": jnp.zeros((), jnp.float32),
            "dropped_fraction": jnp.zeros((), jnp.float32)}


def block_apply(cfg: ModelConfig, kind: str, p: dict, x, positions):
    """Full-sequence block.  Returns (x, aux)."""
    aux = _empty_aux()
    if kind == "S":
        x = x + mamba2.mamba2_block(cfg, p["ssm"],
                                    layers.apply_norm(cfg, p["norm"], x))
        return x, aux
    x = x + attention.attention_block(
        cfg, p["attn"], layers.apply_norm(cfg, p["norm1"], x), positions)
    h = layers.apply_norm(cfg, p["norm2"], x)
    if kind == "M":
        y, aux = moe.moe_block(cfg, p["moe"], h)
    else:
        y = mlp.mlp_block(cfg, p["mlp"], h)
    return x + y, aux


def block_prefill(cfg: ModelConfig, kind: str, p: dict, x, positions, cache):
    if kind == "S":
        y, new_cache = mamba2.mamba2_prefill(
            cfg, p["ssm"], layers.apply_norm(cfg, p["norm"], x), cache)
        return x + y, new_cache
    att, new_cache = attention.attention_prefill(
        cfg, p["attn"], layers.apply_norm(cfg, p["norm1"], x), positions,
        cache)
    x = x + att
    h = layers.apply_norm(cfg, p["norm2"], x)
    if kind == "M":
        y, _ = moe.moe_block(cfg, p["moe"], h)
    else:
        y = mlp.mlp_block(cfg, p["mlp"], h)
    return x + y, new_cache


def block_decode(cfg: ModelConfig, kind: str, p: dict, x, pos, cache, i,
                 live=None):
    """One-token decode of layer ``i``; ``cache`` is the run's stacked
    cache, returned with layer ``i`` updated in place."""
    if kind == "S":
        # SSM state has no positional ring mask — ``live`` only gates
        # attention tiles; dead slots' SSM garbage is masked downstream.
        y, new_layer = mamba2.mamba2_decode(
            cfg, p["ssm"], layers.apply_norm(cfg, p["norm"], x),
            jax.tree.map(lambda c: c[i], cache))
        return x + y, jax.tree.map(
            lambda c, n: jax.lax.dynamic_update_index_in_dim(c, n, i, 0),
            cache, new_layer)
    att, new_cache = attention.attention_decode(
        cfg, p["attn"], layers.apply_norm(cfg, p["norm1"], x), pos, cache,
        i, live=live)
    x = x + att
    h = layers.apply_norm(cfg, p["norm2"], x)
    if kind == "M":
        y, _ = moe.moe_block(cfg, p["moe"], h)
    else:
        y = mlp.mlp_block(cfg, p["mlp"], h)
    return x + y, new_cache


# ------------------------------------------------------------- run drivers

def _maybe_remat(cfg: ModelConfig, fn):
    return jax.checkpoint(fn) if cfg.remat else fn


def _scan(cfg: ModelConfig, body, init, xs):
    return jax.lax.scan(body, init, xs,
                        unroll=True if cfg.unroll_scans else 1)


def apply_runs(cfg: ModelConfig, blocks: dict, x, positions):
    """Forward through all runs (train / plain inference).  Returns
    (x, aux_summed)."""
    total_aux = _empty_aux()
    for (kind, count), run_p in zip(pattern_runs(cfg.layer_pattern),
                                    blocks["runs"]):
        if kind == "G":
            x, _ = _maybe_remat(cfg, lambda h: block_apply(
                cfg, "A", blocks["shared"], h, positions))(x)
            continue

        def body(h, lp, _kind=kind):
            h, aux = block_apply(cfg, _kind, lp, h, positions)
            return h, aux

        x, auxs = _scan(cfg, _maybe_remat(cfg, body), x, run_p)
        total_aux = jax.tree.map(lambda a, b: a + jnp.sum(b),
                                 total_aux, auxs)
    return x, total_aux


def prefill_runs(cfg: ModelConfig, blocks: dict, x, positions, caches):
    new_caches = []
    g_idx = 0
    for (kind, count), run_p, cache in zip(
            pattern_runs(cfg.layer_pattern), blocks["runs"], caches):
        if kind == "G":
            def gbody(h, c):
                return block_prefill(cfg, "A", blocks["shared"], h,
                                     positions, c)
            x, nc = _scan(cfg, lambda h, c: gbody(h, c), x, cache)
            new_caches.append(nc)
            g_idx += 1
            continue

        def body(h, pc, _kind=kind):
            lp, c = pc
            return block_prefill(cfg, _kind, lp, h, positions, c)

        x, nc = _scan(cfg, body, x, (run_p, cache))
        new_caches.append(nc)
    return x, new_caches


def decode_runs(cfg: ModelConfig, blocks: dict, x, pos, caches, live=None):
    """Decode one token through all runs.  Each run's stacked cache rides
    in the layer loop's carry, and layer ``i`` writes its new row in
    place into it: the cache is neither scanned as ``xs`` nor re-stacked
    as ``ys``, so the pool is held once and never copied back."""
    new_caches = []
    for (kind, count), run_p, cache in zip(
            pattern_runs(cfg.layer_pattern), blocks["runs"], caches):

        def body(carry, pi, _kind=kind):
            h, c = carry
            lp, i = pi
            if _kind == "G":
                _kind, lp = "A", blocks["shared"]
            return block_decode(cfg, _kind, lp, h, pos, c, i,
                                live=live), None

        (x, cache), _ = _scan(cfg, body, (x, cache),
                              (run_p, jnp.arange(count)))
        new_caches.append(cache)
    return x, new_caches
