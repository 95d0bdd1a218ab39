"""Operations and bytes the algorithm needs, from shapes and live state.

Counts are of what the computation requires, not of what a kernel
happens to do: a later kernel that skips dead slots or reads less keeps
the same count, so its share of the roofline stays comparable.

  * pool decode attention: each live coded stream attends over its own
    cache up to its position (QK^T and PV), reading its K and V rows;
  * the fused tail: each live group's (N+1, V) coded logits are read and
    contracted into (K, V) decoded logits;
  * a useful token: 2 x the parameters it meets in products (the
    unembedding included; ``matmul_params`` of the configuration's
    architecture module) plus attention at its depth in every layer
    with a KV cache (``dims.layers``).  Only real queries' prompt tokens
    at admission and their generated tokens count: coded redundancy,
    padding rows and recomputed slots do not.
"""

from __future__ import annotations

from typing import Iterable, Tuple

F32 = 4


def attention_flops(dims, keys: int) -> float:
    """One query token over ``keys`` cached keys, one layer."""
    return 4.0 * dims.heads * dims.head_dim * keys


def attention_bytes(dims, keys: int, cache_bytes: int = F32) -> float:
    """K and V rows read, the query read and the output written."""
    kv = 2.0 * keys * dims.kv_heads * dims.head_dim * cache_bytes
    return kv + 2.0 * dims.heads * dims.head_dim * F32


def pool_attention(dims, live_keys: Iterable[Tuple[int, int]]
                   ) -> Tuple[float, float]:
    """(flops, bytes) of one pool decode-attention layer call.
    ``live_keys``: (live streams, keys each attends over) per group."""
    flops = bytes_ = 0.0
    for streams, keys in live_keys:
        flops += streams * attention_flops(dims, keys)
        bytes_ += streams * attention_bytes(dims, keys)
    return flops, bytes_


def tail(k: int, workers: int, vocab: int, live_groups: int
         ) -> Tuple[float, float]:
    """(flops, bytes) of one fused decode over the live groups."""
    flops = 2.0 * k * workers * vocab * live_groups
    bytes_ = F32 * vocab * (workers + k) * live_groups
    return flops, bytes_


def token_flops(arch, dims, depth: int) -> float:
    """A token at position ``depth`` (attending depth + 1 keys)."""
    return (2.0 * arch.matmul_params(dims)
            + dims.layers * attention_flops(dims, depth + 1))


def prompt_flops(arch, dims, prompt_len: int) -> float:
    """A whole prompt, position by position."""
    keys = prompt_len * (prompt_len + 1) / 2.0
    return (prompt_len * 2.0 * arch.matmul_params(dims)
            + dims.layers * 4.0 * dims.heads * dims.head_dim * keys)


def roofline_s(flops: float, bytes_: float, peak_flops: float,
               peak_bw: float) -> Tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    tf, tb = flops / peak_flops, bytes_ / peak_bw
    return (tf, "compute") if tf >= tb else (tb, "memory")
