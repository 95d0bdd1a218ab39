"""Public model API: init / forward / loss / prefill / decode.

Inputs are dicts so every modality has the same entry points:
  text:  {"tokens": (B,S) int32}            (or {"embeddings": (B,S,d)})
  audio: {"frames": (B,T,frontend_dim)}     (stub conv-codec output)
  vlm:   {"patches": (B,P,frontend_dim), "tokens": (B,S_text)}
Optionally {"targets": ...} for the loss.  "embeddings" bypasses the token
table — the entry point the ApproxIFER engine uses for coded queries
(DESIGN.md §4).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models import layers, transformer
from repro.models.config import ModelConfig
from repro.models.partitioning import shard


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def init_params(cfg: ModelConfig, rng) -> dict:
    dtype = _dtype(cfg)
    r1, r2 = jax.random.split(rng)
    return {
        "embeddings": layers.init_embeddings(cfg, r1, dtype),
        "blocks": transformer.init_blocks(cfg, r2, dtype),
        "final_norm": layers.init_norm(cfg, dtype),
    }


def logical_axes(cfg: ModelConfig) -> dict:
    return {
        "embeddings": layers.embeddings_axes(cfg),
        "blocks": transformer.blocks_axes(cfg),
        "final_norm": layers.norm_axes(cfg),
    }


def abstract_params(cfg: ModelConfig):
    """Parameter ShapeDtypeStructs without allocating (dry-run path)."""
    return jax.eval_shape(lambda r: init_params(cfg, r),
                          jax.random.PRNGKey(0))


# --------------------------------------------------------------- embeddings

def embed_inputs(cfg: ModelConfig, params: dict, inputs: dict) -> jnp.ndarray:
    """-> (B, S, d) residual-stream inputs."""
    emb = params["embeddings"]
    if "embeddings" in inputs:
        return inputs["embeddings"].astype(_dtype(cfg))
    parts = []
    if cfg.modality == "audio":
        parts.append(layers.project_frontend(cfg, emb, inputs["frames"]))
    elif cfg.modality == "vlm":
        parts.append(layers.project_frontend(cfg, emb, inputs["patches"]))
        if "tokens" in inputs:
            parts.append(layers.embed_tokens(cfg, emb, inputs["tokens"]))
    else:
        parts.append(layers.embed_tokens(cfg, emb, inputs["tokens"]))
    x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    return shard(x, "batch", "seq", None)


def _positions(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.arange(x.shape[1], dtype=jnp.int32)


# --------------------------------------------------------------- forward

def forward(cfg: ModelConfig, params: dict, inputs: dict
            ) -> Tuple[jnp.ndarray, dict]:
    """Full-sequence forward.  Returns (logits (B,S,V), aux)."""
    x = embed_inputs(cfg, params, inputs)
    x, aux = transformer.apply_runs(cfg, params["blocks"], x, _positions(x))
    x = layers.apply_norm(cfg, params["final_norm"], x)
    logits = layers.unembed(cfg, params["embeddings"], x)
    return shard(logits, "batch", "seq", "vocab"), aux


def predict_fn(cfg: ModelConfig, params: dict):
    """(B, S, d) coded embeddings -> (B, V) last-position logits.

    The black-box ``f`` handed to the ApproxIFER engine: model-agnostic by
    construction — the engine never looks inside.
    """
    def f(embeddings: jnp.ndarray) -> jnp.ndarray:
        logits, _ = forward(cfg, params, {"embeddings": embeddings})
        return logits[:, -1].astype(jnp.float32)

    return f


# --------------------------------------------------------------- losses

def lm_loss(cfg: ModelConfig, params: dict, batch: dict,
            aux_weight: float = 0.01) -> Tuple[jnp.ndarray, dict]:
    """Next-token CE (causal) or masked-frame CE (encoder-only / hubert)."""
    logits, aux = forward(cfg, params, batch)
    logits = logits.astype(jnp.float32)
    if cfg.causal:
        targets = batch.get("targets")
        if targets is None:
            targets = batch["tokens"][:, 1:]
            if cfg.modality == "vlm":
                # loss over the text suffix only (patches are inputs)
                t_len = batch["tokens"].shape[1]
                logits = logits[:, -t_len:-1]
            else:
                logits = logits[:, :-1]
        else:
            # next-token convention: targets[t] is the token AFTER the
            # position whose logits we use, i.e. logits at -(T+1) .. -2
            t = targets.shape[1]
            logits = logits[:, -(t + 1):-1]
    else:
        targets = batch["targets"]            # (B, T) frame labels
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    mask = batch.get("loss_mask")
    if mask is None:
        loss = jnp.mean(nll)
    else:
        mask = mask.astype(jnp.float32)
        loss = jnp.sum(nll * mask) / (jnp.sum(mask) + 1e-6)
    total = loss + aux_weight * (aux["load_balance_loss"]
                                 + 0.1 * aux["router_z_loss"])
    metrics = {"ce_loss": loss,
               "load_balance_loss": aux["load_balance_loss"],
               "dropped_fraction": aux["dropped_fraction"],
               "total_loss": total}
    return total, metrics


# --------------------------------------------------------------- serving

def init_caches(cfg: ModelConfig, batch: int, max_len: int,
                dtype=None) -> list:
    dtype = dtype or _dtype(cfg)
    return transformer.init_run_caches(cfg, batch, max_len, dtype)


def cache_axes(cfg: ModelConfig) -> list:
    return transformer.run_cache_axes(cfg)


def prefill(cfg: ModelConfig, params: dict, inputs: dict, caches: list
            ) -> Tuple[jnp.ndarray, list]:
    """Process the full prompt; returns (last-token logits (B,V), caches)."""
    x = embed_inputs(cfg, params, inputs)
    with jax.named_scope("blocks"):
        x, caches = transformer.prefill_runs(cfg, params["blocks"], x,
                                             _positions(x), caches)
    with jax.named_scope("unembed"):
        x = layers.apply_norm(cfg, params["final_norm"], x[:, -1:])
        logits = layers.unembed(cfg, params["embeddings"], x)[:, 0]
    return logits.astype(jnp.float32), caches


def decode_step(cfg: ModelConfig, params: dict, caches: list, inputs: dict,
                pos: jnp.ndarray,
                live: jnp.ndarray = None) -> Tuple[jnp.ndarray, list]:
    """One decode step.  inputs: {"tokens": (B,1)} or {"embeddings":
    (B,1,d)}; pos: scalar int32 current position, or (B,) int32 per-stream
    positions (slot-pool continuous batching, DESIGN.md §10); live:
    optional (B,) slot-live mask handed to the pool attention kernel
    (dead streams' attention tiles are skipped in-kernel — their rows
    are garbage either way and must be masked downstream).
    -> (logits (B,V), caches).
    """
    if "embeddings" in inputs:
        x = inputs["embeddings"].astype(_dtype(cfg))
    else:
        x = layers.embed_tokens(cfg, params["embeddings"], inputs["tokens"])
    x = shard(x, "batch", None, None)
    with jax.named_scope("blocks"):
        x, caches = transformer.decode_runs(cfg, params["blocks"], x, pos,
                                            caches, live=live)
    with jax.named_scope("unembed"):
        x = layers.apply_norm(cfg, params["final_norm"], x)
        logits = layers.unembed(cfg, params["embeddings"], x)[:, 0]
    return logits.astype(jnp.float32), caches
