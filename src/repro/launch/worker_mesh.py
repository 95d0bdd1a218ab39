"""Worker-axis sharding for the coded stream pool (DESIGN.md §13).

ApproxIFER's premise is that the N+1 coded queries of a group run on
*distinct workers*; here a worker is a rank along the "worker" mesh axis
(``launch.mesh.make_worker_mesh``).  Coded streams are laid out
**worker-major** — the flat stream axis is ``(N+1, G)`` flattened, so a
contiguous 1/W slice of it is exactly the streams owned by one worker
rank.  The encode side produces this layout directly:
``ops.berrut_encode_dispatch`` fuses the Berrut contraction with the
per-rank stream order in one HBM pass (no post-encode swapaxes), so
sharding the streams over the "worker" axis is a constraint, not a
copy.  The decode tail gathers **only survivor shards**:

  1. every rank scatters its local streams into a ``(width, G, V)``
     buffer at their survivor-compacted slot (non-survivors are dropped),
  2. one ``psum_scatter`` over the vocab axis sums the buffers —
     moving ``width/(N+1)`` of the bytes an all-gather of the full
     coded block would move — leaving a vocab-sharded compacted block,
  3. the fused decode contracts the compacted ``(G, width, V/W)`` block
     against the survivor-compacted Berrut basis (compaction is exact:
     ``berrut.survivor_weights`` signs depend only on survivor *rank*,
     which order-preserving compaction keeps), and
  4. sampling runs on the vocab shard (hierarchical argmax / merged
     top-k with the same tie-breaks as ``sampling.sample_tokens``), so
     the sample path never materialises full logits anywhere.

The ``worker=1`` / off-mesh degenerate path runs the *same* compacted
math without collectives, so results are bit-identical across worker
counts; ``mode="replicated"`` keeps the all-gather-everything baseline
for the ``fig_mesh_serving`` comparison.
"""

from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.berrut import CodingConfig
from repro.kernels import ops
from repro.models import partitioning

if TYPE_CHECKING:  # import cycle: serving.coded_serving imports this module
    from repro.serving.sampling import SampleConfig


def _sample_tokens(logits, sample, rng):
    from repro.serving.sampling import sample_tokens
    return sample_tokens(logits, sample, rng)

def _smap(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


@dataclasses.dataclass(frozen=True)
class WorkerShardConfig:
    """Static worker-sharding policy — hashable, baked into the trace
    like ``CodingConfig`` (changing it is a retrace, not a branch).

    gather_width: survivor slots gathered at decode.  ``None`` resolves
    to ``coding.decode_quorum`` — the most streams a round can wait for
    under the scheduler's ``apply_pool_state`` policy.  If a straggler
    mask ever carries MORE survivors than the width, only the first
    ``width`` (lowest worker index) are decoded; schedulers that wait
    beyond the quorum must widen this explicitly (they raise otherwise).

    mode: "survivor" (masked gather of <= width shards) or "replicated"
    (all-gather of all N+1 — the baseline ``fig_mesh_serving`` beats).
    """

    axis: str = "worker"
    gather_width: Optional[int] = None
    mode: str = "survivor"

    def __post_init__(self):
        if self.mode not in ("survivor", "replicated"):
            raise ValueError(f"unknown worker-shard mode {self.mode!r}")
        if self.gather_width is not None and self.gather_width < 1:
            raise ValueError(f"gather_width must be >= 1, "
                             f"got {self.gather_width}")

    def resolved_width(self, coding: CodingConfig) -> int:
        w = self.gather_width or coding.decode_quorum
        return min(w, coding.num_workers)


def worker_axis_size(wshard: Optional[WorkerShardConfig]) -> int:
    """Size of the worker mesh axis in the ACTIVE sharding context (1
    when off-mesh or the mesh has no such axis — the degenerate path)."""
    mesh = partitioning.active_mesh()
    if wshard is None or mesh is None:
        return 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get(wshard.axis, 1)


def validate_layout(coding: CodingConfig, wshard: WorkerShardConfig) -> int:
    """Check the worker-major layout is shardable; returns the axis size."""
    w = worker_axis_size(wshard)
    if coding.num_workers % w != 0:
        raise ValueError(
            f"coded pool of {coding.num_workers} workers cannot shard "
            f"over a {w}-way {wshard.axis!r} mesh axis (need divisibility "
            f"so each rank owns whole streams)")
    return w


def _survivor_slots(avail: jnp.ndarray, width: int):
    """Compacted slot assignment for the survivor gather.

    avail: (N+1,) 0/1 availability.  Returns (slots (N+1,) int32 — the
    compacted destination of each stream, ``width`` = dropped; idx
    (width,) int32 — the source stream of each slot, 0 for empty slots;
    slot_valid (width,) — 1.0 while slots hold a real survivor).
    Compaction preserves stream order, so survivor *ranks* — the only
    thing ``berrut.survivor_weights`` signs depend on — are unchanged.
    """
    u = (avail > 0).astype(jnp.int32)
    pos = jnp.cumsum(u) - 1
    slots = jnp.where((u > 0) & (pos < width), pos, width)
    idx = (jnp.zeros((width + 1,), jnp.int32)
           .at[slots].set(jnp.arange(u.shape[0], dtype=jnp.int32))[:width])
    nsurv = jnp.minimum(jnp.sum(u), width)
    slot_valid = (jnp.arange(width) < nsurv).astype(jnp.float32)
    return slots, idx, slot_valid


def _decode_rows(grouped: jnp.ndarray, masks: jnp.ndarray,
                 alphas: jnp.ndarray, betas: jnp.ndarray,
                 row_mask: Optional[jnp.ndarray]) -> jnp.ndarray:
    """(G, S, V') coded block -> (G*K, V') decoded real-query rows."""
    dec = ops.fused_group_decode(grouped, masks, alphas, betas)
    dec = dec.reshape(-1, dec.shape[-1])
    if row_mask is not None:
        dec = dec * row_mask[:, None].astype(dec.dtype)
    return dec


def _sample_vocab_sharded(logits: jnp.ndarray, config: SampleConfig,
                          rng: Optional[jax.Array], axis: str, w: int,
                          vloc: int) -> jnp.ndarray:
    """``sampling.sample_tokens`` over a vocab-sharded (rows, V/W) block.

    Bit-identical to the replicated version: greedy breaks ties to the
    lowest global index (argmax over the rank-ordered candidate table),
    and merged per-rank top-k preserves the full-vocab top-k value/index
    order (a global top-k element is always in its rank's local top-k;
    rank-major concatenation keeps equal values in global-index order).
    """
    r = jax.lax.axis_index(axis)
    offset = (r * vloc).astype(jnp.int32)
    if config.top_k <= 1:
        li = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        lv = jnp.take_along_axis(logits, li[:, None], axis=-1)[:, 0]
        gv = jax.lax.all_gather(lv, axis)                # (W, rows)
        gi = jax.lax.all_gather(li + offset, axis)
        best = jnp.argmax(gv, axis=0)                    # ties -> low rank
        return jnp.take_along_axis(gi, best[None, :], axis=0)[0]
    if rng is None:
        raise ValueError("top_k > 1 sampling needs an rng key")
    kk = config.top_k
    lv, li = jax.lax.top_k(logits.astype(jnp.float32), kk)
    gv = jax.lax.all_gather(lv, axis)                    # (W, rows, kk)
    gi = jax.lax.all_gather(li.astype(jnp.int32) + offset, axis)
    rows = logits.shape[0]
    gv = jnp.moveaxis(gv, 0, 1).reshape(rows, w * kk)
    gi = jnp.moveaxis(gi, 0, 1).reshape(rows, w * kk)
    vals, sel = jax.lax.top_k(gv, kk)
    idx = jnp.take_along_axis(gi, sel, axis=-1)
    choice = jax.random.categorical(rng, vals / config.temperature,
                                    axis=-1)
    return jnp.take_along_axis(
        idx, choice[..., None], axis=-1)[..., 0].astype(jnp.int32)


def survivor_decode_tail(coding: CodingConfig, block: jnp.ndarray,
                         masks: jnp.ndarray, avail: jnp.ndarray,
                         wshard: WorkerShardConfig, *,
                         row_mask: Optional[jnp.ndarray] = None,
                         sample: Optional[SampleConfig] = None,
                         sample_rng: Optional[jax.Array] = None):
    """Decode tail over worker-major coded logits.

    block: (N+1, G, V) worker-major coded logits (flat stream axis
    reshaped); masks: (G, N+1) float decode masks (availability with the
    locator's exclusions already composed in); avail: (N+1,) float
    availability — defines the shared survivor slots; row_mask: optional
    (G*K,) live-row mask applied to decoded rows before sampling.
    Returns (G*K,) sampled int32 tokens with ``sample``, else (G*K, V)
    decoded logits.
    """
    n1, g, v = block.shape
    assert n1 == coding.num_workers
    w = validate_layout(coding, wshard)
    width = wshard.resolved_width(coding)
    alphas = jnp.asarray(coding.alphas, jnp.float32)
    betas = jnp.asarray(coding.betas, jnp.float32)
    mf = masks.astype(jnp.float32)

    if wshard.mode == "replicated":
        if w == 1:
            grouped = jnp.swapaxes(block, 0, 1)
            dec = _decode_rows(grouped, mf, alphas, betas, row_mask)
            return dec if sample is None else _sample_tokens(dec, sample,
                                                            sample_rng)
        return _replicated_tail(block, mf, alphas, betas, wshard, w,
                                row_mask, sample, sample_rng)

    slots, idx, slot_valid = _survivor_slots(avail, width)
    masks_c = jnp.take(mf, idx, axis=1) * slot_valid[None, :]
    betas_c = jnp.take(betas, idx)
    if w == 1:
        taken = jnp.take(block, idx, axis=0)             # (width, G, V)
        grouped = jnp.swapaxes(taken, 0, 1)              # (G, width, V)
        dec = _decode_rows(grouped, masks_c, alphas, betas_c, row_mask)
        return dec if sample is None else _sample_tokens(dec, sample,
                                                        sample_rng)
    return _survivor_tail(block, masks_c, betas_c, slots, alphas, wshard,
                          w, width, row_mask, sample, sample_rng)


def _dummy_rng():
    return jnp.zeros((2,), jnp.uint32)


def _survivor_tail(block, masks_c, betas_c, slots, alphas, wshard, w,
                   width, row_mask, sample, sample_rng):
    """shard_map survivor gather: compact-scatter + psum_scatter over
    vocab, vocab-sharded fused decode, vocab-sharded sampling."""
    mesh = partitioning.active_mesh()
    axis = wshard.axis
    n1, g, v = block.shape
    nl = n1 // w
    # psum_scatter needs the vocab divisible by W; merged top-k needs
    # each rank to hold >= top_k vocab entries.  Otherwise fall back to
    # a full psum of the compacted buffer (still < the all-gather when
    # width < (N+1)/2).
    scatter_v = v % w == 0 and (sample is None or sample.top_k <= v // w)
    rng = sample_rng if sample_rng is not None else _dummy_rng()
    rmask = (row_mask if row_mask is not None
             else jnp.ones((0,), jnp.float32))
    has_row_mask = row_mask is not None

    def body(local, masks_c, betas_c, slots, rng, rmask):
        r = jax.lax.axis_index(axis)
        local_slots = jax.lax.dynamic_slice_in_dim(slots, r * nl, nl)
        # scatter local streams to their compacted slot; non-survivors
        # land in the spill row [width] and are sliced off
        buf = (jnp.zeros((width + 1, g, v), local.dtype)
               .at[local_slots].set(local)[:width])
        if scatter_v:
            part = jax.lax.psum_scatter(buf, axis, scatter_dimension=2,
                                        tiled=True)      # (width, G, V/W)
        else:
            part = jax.lax.psum(buf, axis)               # (width, G, V)
        grouped = jnp.swapaxes(part, 0, 1)
        dec = _decode_rows(grouped, masks_c, alphas, betas_c,
                           rmask if has_row_mask else None)
        if sample is None:
            if scatter_v:
                dec = jax.lax.all_gather(dec, axis, axis=1, tiled=True)
            return dec
        if not scatter_v:
            return _sample_tokens(dec, sample, rng)
        return _sample_vocab_sharded(dec, sample, rng, axis, w, v // w)

    in_specs = (P(axis, None, None), P(None, None), P(None), P(None),
                P(None), P(None))
    out_specs = P(None) if sample is not None else P(None, None)
    fn = _smap(body, mesh, in_specs, out_specs)
    return fn(block, masks_c, betas_c, slots, rng, rmask)


def _replicated_tail(block, masks, alphas, betas, wshard, w, row_mask,
                     sample, sample_rng):
    """The baseline: all-gather every coded stream, decode replicated."""
    mesh = partitioning.active_mesh()
    axis = wshard.axis
    rng = sample_rng if sample_rng is not None else _dummy_rng()
    rmask = (row_mask if row_mask is not None
             else jnp.ones((0,), jnp.float32))
    has_row_mask = row_mask is not None

    def body(local, masks, betas, rng, rmask):
        full = jax.lax.all_gather(local, axis, axis=0,
                                  tiled=True)            # (N+1, G, V)
        grouped = jnp.swapaxes(full, 0, 1)
        dec = _decode_rows(grouped, masks, alphas, betas,
                           rmask if has_row_mask else None)
        if sample is None:
            return dec
        return _sample_tokens(dec, sample, rng)

    in_specs = (P(axis, None, None), P(None, None), P(None), P(None),
                P(None))
    out_specs = P(None) if sample is not None else P(None, None)
    fn = _smap(body, mesh, in_specs, out_specs)
    return fn(block, masks, betas, rng, rmask)
