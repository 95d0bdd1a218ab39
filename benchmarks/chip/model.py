"""Sizes and coding point of a configuration file, as plain numbers.

Reads ``configs/<name>.json`` (Hugging Face ``config.json`` keys plus
the benchmark's own) and nothing of the program, so the reference and
the work counts stand apart from the code under test.
"""

from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    rope_theta: float
    eps: float
    embed_mult: float             # token embedding scale (see departures)
    dtype: str                    # the dtype the configuration serves

    @property
    def params_matmul(self) -> int:
        """Parameters a token meets in matrix products: every layer's
        projections and MLP, plus the unembedding (the tied table)."""
        d, h, kv, hd, f = (self.hidden, self.heads, self.kv_heads,
                           self.head_dim, self.ffn)
        per_layer = d * h * hd * 2 + d * kv * hd * 2 + 3 * d * f
        return self.layers * per_layer + self.vocab * d


@dataclasses.dataclass(frozen=True)
class Coding:
    k: int
    s: int
    e: int

    @property
    def workers(self) -> int:
        """N + 1 coded streams per group of K queries."""
        return self.k + self.s if self.e == 0 else \
            2 * (self.k + self.e) + self.s

    @property
    def quorum(self) -> int:
        """Workers a round waits for: K, or K + 2E for the locator."""
        return self.k if self.e == 0 else min(self.k + 2 * self.e,
                                              self.workers)


def dims(config: dict) -> Dims:
    dep = config.get("departures", {})
    return Dims(
        layers=int(config["num_hidden_layers"]),
        hidden=int(config["hidden_size"]),
        heads=int(config["num_attention_heads"]),
        kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        ffn=int(config["intermediate_size"]),
        vocab=int(config["vocab_size"]),
        rope_theta=float(config["rope_theta"]),
        eps=float(config["rms_norm_eps"]),
        embed_mult=(math.sqrt(config["hidden_size"])
                    if dep.get("embedding_times_sqrt_hidden") else 1.0),
        dtype=config["torch_dtype"])


def coding(config: dict) -> Coding:
    c = config["coding"]
    return Coding(k=int(c["k"]), s=int(c["s"]), e=int(c["e"]))
