"""Coded serving steps — the paper's protocol integrated INSIDE the jitted
serving program (DESIGN.md §5).

Every coded stream owns its own KV cache / SSM state: the cache of a
stream is the cache of its coded embedding history, so stragglers and
Byzantine workers can be masked at ANY decode step without recomputation.

Shapes: G query groups x K real queries; N+1 coded streams per group.
The coded-stream axis (G*(N+1)) is the batch axis the mesh shards over
("pod","data") — a "worker" is the device slice owning one coded stream.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import berrut
from repro.core.berrut import CodingConfig
from repro.core.error_locator import gather_vote_values, locate_groups
from repro.kernels import ops
from repro.models import decode_step, embed_inputs, init_caches, prefill
from repro.models.config import ModelConfig
from repro.launch.worker_mesh import WorkerShardConfig
from repro.models.partitioning import shard
from repro.serving.sampling import SampleConfig, sample_tokens


def num_padded_streams(coding: CodingConfig, groups: int) -> int:
    """Coded streams padded to the mesh batch-axes product (see
    partitioning.padded_batch — uneven batches make GSPMD replicate)."""
    from repro.models.partitioning import padded_batch
    return padded_batch(groups * coding.num_workers)


def _code_streams(coding: CodingConfig, x: jnp.ndarray,
                  worker_major: bool = False) -> jnp.ndarray:
    """(G, K, ...) -> (padded_streams, ...) coded streams via the Berrut
    encode contraction (kernel-dispatched).  Padding streams repeat stream
    0 and are sliced off after decode.

    Default layout is group-major (stream ``g*(N+1) + n``).  With
    ``worker_major`` the flat axis is ``n*G + g`` so a contiguous 1/W
    slice along it is exactly one worker rank's streams — what the
    "worker" mesh axis shards (DESIGN.md §13).  Worker-major requires
    exact divisibility (no padding streams: appending them would break
    the (N+1, G) block structure)."""
    g = x.shape[0]
    w = berrut.encode_matrix(coding).astype(x.dtype)      # (N+1, K)
    flat = x.reshape(g, coding.k, -1)
    # G is tiny; parallelise the coding contraction over the feature axis
    # (full mesh), then reshard to the batch layout.
    flat = shard(flat, None, None, "coded_flat")
    if worker_major:
        if num_padded_streams(coding, g) != g * coding.num_workers:
            raise ValueError(
                "worker-major coded streams cannot be padded: "
                f"{g * coding.num_workers} streams vs mesh batch product "
                f"{num_padded_streams(coding, g)} (make N+1 divisible "
                "by the worker axis)")
        # One-pass encode->dispatch: the kernel writes each coded tile
        # straight into the flat ``n*G + g`` per-rank layout — no
        # post-encode swapaxes/reshape pass over the coded block.
        coded = ops.berrut_encode_dispatch(w, flat)       # ((N+1)*G, F)
        coded = coded.reshape(g * coding.num_workers, *x.shape[2:])
        return shard(coded, "batch", *([None] * (coded.ndim - 1)))
    coded = ops.berrut_apply(w, flat)                     # (G, N+1, F)
    coded = shard(coded, None, None, "coded_flat")
    coded = coded.reshape(g * coding.num_workers, *x.shape[2:])
    pad = num_padded_streams(coding, g) - coded.shape[0]
    if pad:
        coded = jnp.concatenate(
            [coded, jnp.broadcast_to(coded[:1], (pad,) + coded.shape[1:])],
            axis=0)
    return shard(coded, "batch", *([None] * (coded.ndim - 1)))


def _real_streams(coding: CodingConfig, coded_logits: jnp.ndarray,
                  groups: int) -> jnp.ndarray:
    """Drop the divisibility-padding streams before decoding."""
    return coded_logits[: groups * coding.num_workers]


def locate(coding: CodingConfig, coded_logits: jnp.ndarray,
           avail: jnp.ndarray, worker_major: bool = False,
           locate_quorum: Optional[jnp.ndarray] = None
           ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Vote-gated Algorithm 2 per group over in-program coded logits.

    Shares ``core.error_locator.locate_groups`` with the engine's jitted
    ``locate_and_decode``, so the offline serving steps and the online
    scheduler locate bit-identically given the same logits and mask.
    The vote coordinates are gathered from the raw block BEFORE the
    float32 upcast (``gather_vote_values``): only the (G, N+1, C_vote)
    slice is ever cast, never a full copy of the coded-logit block.

    ``locate_quorum`` (a traced int32 scalar, DESIGN.md §15) gates the
    verdicts per ROUND instead of per trace: when fewer than
    ``locate_quorum`` streams are available the locator's exclusions are
    suppressed (below the K+2E budget error location is hopeless — the
    host-side ``EngineExecutor`` makes the same call, but there the
    quorum is a Python branch; here it must be data so re-planned rounds
    don't retrace).  ``None`` keeps the unconditional verdicts.

    coded_logits: (G*(N+1), V).  Returns (per-group decode masks (G, N+1),
    located (G, N+1) bool, votes (G, N+1) int32); with E == 0 the masks
    collapse to broadcasting ``avail`` and nothing is located.
    """
    g = coded_logits.shape[0] // coding.num_workers
    if coding.e == 0:
        masks = jnp.broadcast_to(avail, (g, coding.num_workers))
        zeros = jnp.zeros((g, coding.num_workers), jnp.int32)
        return masks, zeros.astype(bool), zeros
    if worker_major:
        # (N+1, G, V) blocks: gather the tiny vote slice first, THEN
        # transpose — only (N+1, G, C_vote) values ever move
        vals = jnp.swapaxes(gather_vote_values(
            coded_logits.reshape(coding.num_workers, g, -1),
            coding.c_vote), 0, 1)
    else:
        vals = gather_vote_values(
            coded_logits.reshape(g, coding.num_workers, -1), coding.c_vote)
    betas = jnp.asarray(coding.betas, jnp.float32)
    located, votes = locate_groups(betas, vals, avail,
                                   k=coding.k, e=coding.e)
    if locate_quorum is not None:
        located = jnp.logical_and(
            located, jnp.sum(avail) >= locate_quorum)
    masks = avail[None, :] * (1.0 - located.astype(avail.dtype))
    return masks, located, votes


def _corrupt_logits(coding: CodingConfig, coded_logits: jnp.ndarray,
                    byz_mask: jnp.ndarray, byz_rng: jax.Array,
                    sigma: float, collude: bool,
                    worker_major: bool = False) -> jnp.ndarray:
    """Byzantine workers corrupt their coded logits (paper §4.2).  With
    ``collude`` every compromised worker in a group tells the SAME lie.

    The noise draw is layout-aware so group-major and worker-major runs
    corrupt stream (n, g) with the SAME value given the same rng.
    """
    g = coded_logits.shape[0] // coding.num_workers
    v = coded_logits.shape[-1]
    if collude:
        noise = jax.random.normal(byz_rng, (g, 1, v), coded_logits.dtype)
        noise = jnp.broadcast_to(noise, (g, coding.num_workers, v))
    else:
        noise = jax.random.normal(
            byz_rng, (g, coding.num_workers, v), coded_logits.dtype)
    if worker_major:
        noise = jnp.swapaxes(noise, 0, 1)
        per_stream = jnp.repeat(byz_mask, g)
    else:
        per_stream = jnp.tile(byz_mask, (g,))
    return (coded_logits
            + sigma * per_stream[:, None]
            * noise.reshape(g * coding.num_workers, v))


# Trace-time side effects: incremented once per jit compilation of the
# coded serving steps (legacy batch-scoped or slot-pool continuous) — the
# compile-count guards in tests assert a whole serving run traces prefill
# and decode-step exactly once each.  Outside jit they count calls.
CODED_PREFILL_TRACES = 0
CODED_DECODE_STEP_TRACES = 0


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CodedServingState:
    """Carried between serving steps (a pytree)."""

    caches: list                   # per-run coded-stream caches
    pos: jnp.ndarray               # () int32 — next position to write


def _compose_live(straggler_mask: Optional[jnp.ndarray],
                  live_mask: Optional[jnp.ndarray]
                  ) -> Optional[jnp.ndarray]:
    """Compose the per-stream ``live_mask`` of the current operating
    point into the round's straggler mask (DESIGN.md §15): a retune to a
    narrower (N, E) masks off the trailing coded streams exactly like
    stragglers, so the one max-width program serves every operating
    point.  A ``live_mask`` of ones (or None) is bit-identical to the
    pre-replan program: ``x * 1.0 == x`` exactly in float."""
    if live_mask is None:
        return straggler_mask
    if straggler_mask is None:
        return live_mask
    return straggler_mask * live_mask


def _finish_round(coding: CodingConfig, coded_logits: jnp.ndarray,
                  straggler_mask: Optional[jnp.ndarray], with_report: bool,
                  locate_quorum: Optional[jnp.ndarray] = None):
    """Shared tail of every coded round: locate -> exclude -> decode,
    fused (DESIGN.md §11).

    The pre-fused path paid for the (G, N+1, V) coded-logit block three
    times: a full float32 upcast materialised just so the locator could
    read C_vote strided columns of it, (G, K, N+1) per-group decode
    matrices built in XLA and round-tripped through memory, and a
    separate vmapped contraction.  Now the locator reads the strided
    vote columns straight off the raw block (``gather_vote_values``,
    cast AFTER the gather) and the decode is one
    ``ops.fused_group_decode`` pass — per-group survivor-weight matrix
    construction fused into the contraction (in VMEM on the TPU kernel
    path), masks straight from the gated locator verdicts.
    """
    avail = (straggler_mask if straggler_mask is not None
             else jnp.ones((coding.num_workers,), jnp.float32))
    v = coded_logits.shape[-1]
    g = coded_logits.shape[0] // coding.num_workers
    # ONE locate definition: the same ``locate`` the offline verifiers
    # call produces the per-group exclusion masks the fused decode eats
    masks, located, votes = locate(coding, coded_logits, avail,
                                   locate_quorum=locate_quorum)
    grouped = coded_logits.reshape(g, coding.num_workers, v)
    logits = ops.fused_group_decode(
        grouped, masks.astype(jnp.float32),
        jnp.asarray(coding.alphas, jnp.float32),
        jnp.asarray(coding.betas, jnp.float32))
    logits = logits.reshape(g * coding.k, v)
    if with_report:
        return logits, (located, votes)
    return logits, None


def _finish_round_wm(coding: CodingConfig, coded_logits: jnp.ndarray,
                     straggler_mask: Optional[jnp.ndarray],
                     with_report: bool, wshard: WorkerShardConfig,
                     sample: Optional[SampleConfig],
                     sample_rng: Optional[jax.Array],
                     row_mask: Optional[jnp.ndarray] = None,
                     locate_quorum: Optional[jnp.ndarray] = None):
    """Worker-sharded round tail (DESIGN.md §13).

    The coded logits arrive worker-major — stream ``n*G + g`` — so the
    flat axis shards contiguously over the "worker" mesh axis.  Locate
    runs on the tiny vote slice exactly as in ``_finish_round``; the
    decode is the survivor-only gather + compacted fused decode +
    on-shard sampling of ``launch.worker_mesh.survivor_decode_tail``
    (sampling must happen inside the sharded tail so the full decoded
    logits never materialise on one device).  Returns ``(out, report)``
    where ``out`` is (G*K,) token ids with ``sample`` else (G*K, V)
    logits.
    """
    from repro.launch import worker_mesh
    avail = (straggler_mask if straggler_mask is not None
             else jnp.ones((coding.num_workers,), jnp.float32))
    v = coded_logits.shape[-1]
    g = coded_logits.shape[0] // coding.num_workers
    masks, located, votes = locate(coding, coded_logits, avail,
                                   worker_major=True,
                                   locate_quorum=locate_quorum)
    block = coded_logits.reshape(coding.num_workers, g, v)
    out = worker_mesh.survivor_decode_tail(
        coding, block, masks, avail, wshard, row_mask=row_mask,
        sample=sample, sample_rng=sample_rng)
    return out, ((located, votes) if with_report else None)


def _maybe_sample(logits: jnp.ndarray, sample: Optional[SampleConfig],
                  sample_rng: Optional[jax.Array]) -> jnp.ndarray:
    """On-device token selection (DESIGN.md §11): with a ``SampleConfig``
    the step returns (G*K,) int32 token ids instead of (G*K, V) logits,
    so the round loop's device->host transfer shrinks by a factor of V
    and the host bookkeeping overlaps the next dispatched round."""
    if sample is None:
        return logits
    return sample_tokens(logits, sample, sample_rng)


def coded_prefill(cfg: ModelConfig, coding: CodingConfig, params: dict,
                  inputs: dict, max_len: int,
                  straggler_mask: Optional[jnp.ndarray] = None,
                  cache_dtype=None,
                  byz_mask: Optional[jnp.ndarray] = None,
                  byz_rng: Optional[jax.Array] = None,
                  byz_sigma: float = 10.0, byz_collude: bool = False,
                  with_report: bool = False,
                  sample: Optional[SampleConfig] = None,
                  sample_rng: Optional[jax.Array] = None,
                  wshard: Optional[WorkerShardConfig] = None,
                  live_mask: Optional[jnp.ndarray] = None,
                  locate_quorum: Optional[jnp.ndarray] = None):
    """Prefill G*K real prompts as G*(N+1) coded streams.

    inputs: modality dict with leading batch = G*K real queries.
    Byzantine workers (``byz_mask``) corrupt their prefill logits exactly
    like a decode step's — the adversary does not wait for decode rounds.
    ``live_mask`` masks off the coded streams beyond the current
    operating point's width and ``locate_quorum`` gates the locator's
    verdicts per round (masked max-width re-planning, DESIGN.md §15);
    both default to the static single-operating-point behavior.
    Returns (decoded last-token logits (G*K, V) — or, with ``sample``,
    on-device-sampled (G*K,) int32 token ids — and the serving state);
    with ``with_report`` also the (located, votes) pair of the vote-gated
    locator for reputation tracking.
    """
    global CODED_PREFILL_TRACES
    CODED_PREFILL_TRACES += 1
    straggler_mask = _compose_live(straggler_mask, live_mask)
    x = embed_inputs(cfg, params, inputs)                 # (G*K, S, d)
    gk, s, d = x.shape
    g = gk // coding.k
    wm = wshard is not None
    coded = _code_streams(coding, x.reshape(g, coding.k, s, d),
                          worker_major=wm)
    caches = init_caches(cfg, coded.shape[0], max_len,
                         dtype=cache_dtype or coded.dtype)
    coded_logits, caches = prefill(cfg, params, {"embeddings": coded},
                                   caches)
    coded_logits = _real_streams(coding, coded_logits, g)
    if byz_mask is not None and byz_rng is not None:
        coded_logits = _corrupt_logits(coding, coded_logits, byz_mask,
                                       byz_rng, byz_sigma, byz_collude,
                                       worker_major=wm)
    if wm:
        out, report = _finish_round_wm(coding, coded_logits,
                                       straggler_mask, with_report,
                                       wshard, sample, sample_rng,
                                       locate_quorum=locate_quorum)
    else:
        logits, report = _finish_round(coding, coded_logits,
                                       straggler_mask, with_report,
                                       locate_quorum=locate_quorum)
        out = _maybe_sample(logits, sample, sample_rng)
    state = CodedServingState(caches=caches,
                              pos=jnp.asarray(s, jnp.int32))
    if with_report:
        return out, state, report
    return out, state


def coded_decode_step(cfg: ModelConfig, coding: CodingConfig, params: dict,
                      state: CodedServingState, tokens: jnp.ndarray,
                      straggler_mask: Optional[jnp.ndarray] = None,
                      byz_mask: Optional[jnp.ndarray] = None,
                      byz_rng: Optional[jax.Array] = None,
                      byz_sigma: float = 10.0, byz_collude: bool = False,
                      with_report: bool = False,
                      sample: Optional[SampleConfig] = None,
                      sample_rng: Optional[jax.Array] = None,
                      wshard: Optional[WorkerShardConfig] = None,
                      live_mask: Optional[jnp.ndarray] = None,
                      locate_quorum: Optional[jnp.ndarray] = None):
    """One coded decode step.

    tokens: (G*K, 1) int32 — the sampled next token of each REAL stream.
    The K token embeddings of each group are Berrut-encoded into N+1 coded
    embeddings appended to the coded caches (DESIGN.md §5).  With
    ``byz_collude`` every Byzantine worker in a group adds the SAME noise
    (the colluding adversary of ``serving.failures``).  ``live_mask`` /
    ``locate_quorum`` re-plan the operating point per round without
    retracing (DESIGN.md §15).
    Returns (decoded logits (G*K, V) — or sampled (G*K,) token ids with
    ``sample`` — and the new state); with ``with_report`` also the
    locator's (located, votes).
    """
    global CODED_DECODE_STEP_TRACES
    CODED_DECODE_STEP_TRACES += 1
    straggler_mask = _compose_live(straggler_mask, live_mask)
    from repro.models import layers as _layers
    x = _layers.embed_tokens(cfg, params["embeddings"], tokens)  # (G*K,1,d)
    gk, _, d = x.shape
    g = gk // coding.k
    wm = wshard is not None
    coded = _code_streams(coding, x.reshape(g, coding.k, 1, d),
                          worker_major=wm)
    coded_logits, caches = decode_step(cfg, params, state.caches,
                                       {"embeddings": coded}, state.pos)
    coded_logits = _real_streams(coding, coded_logits, g)
    if byz_mask is not None and byz_rng is not None:
        coded_logits = _corrupt_logits(coding, coded_logits, byz_mask,
                                       byz_rng, byz_sigma, byz_collude,
                                       worker_major=wm)
    if wm:
        out, report = _finish_round_wm(coding, coded_logits,
                                       straggler_mask, with_report,
                                       wshard, sample, sample_rng,
                                       locate_quorum=locate_quorum)
    else:
        logits, report = _finish_round(coding, coded_logits,
                                       straggler_mask, with_report,
                                       locate_quorum=locate_quorum)
        out = _maybe_sample(logits, sample, sample_rng)
    new_state = CodedServingState(caches=caches, pos=state.pos + 1)
    if with_report:
        return out, new_state, report
    return out, new_state


# --------------------------------------------------------- slot pool (§10)
#
# Continuous batching over a fixed-capacity coded-stream slot pool: the
# jitted program ALWAYS runs pool_groups x (N+1) coded streams.  A group
# slot is either live (its group decodes every round) or free (its
# streams compute masked garbage); groups join at prefill mid-flight into
# free slots, retire independently, and a retired slot's caches are
# simply overwritten by the next admission's prefill.  Because every
# shape is pinned to the pool size, deadline-flushed partial batches and
# mid-flight admissions never change the traced program — prefill and
# decode-step each compile exactly once per serving run.


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CodedPoolState:
    """Persistent slot-pool serving state (a pytree).

    ``caches`` hold the coded-stream KV/SSM state of every slot in the
    pool; ``pos`` is the per-GROUP-slot next cache position (all N+1
    coded streams of a group advance in lockstep — DESIGN.md §5's
    stream-owns-its-cache invariant, sliced per slot)."""

    caches: list                   # pool-wide coded-stream caches
    pos: jnp.ndarray               # (pool_groups,) int32 per-slot position


def init_pool_state(cfg: ModelConfig, coding: CodingConfig,
                    pool_groups: int, max_len: int,
                    cache_dtype=None) -> CodedPoolState:
    """Allocate the fixed slot pool: ``pool_groups * (N+1)`` coded-stream
    caches (padded to the mesh batch product) and zeroed slot positions."""
    if pool_groups < 1:
        raise ValueError(f"need pool_groups >= 1, got {pool_groups}")
    streams = num_padded_streams(coding, pool_groups)
    dtype = cache_dtype or jnp.dtype(cfg.param_dtype)
    caches = init_caches(cfg, streams, max_len, dtype=dtype)
    return CodedPoolState(caches=caches,
                          pos=jnp.zeros((pool_groups,), jnp.int32))


def _stream_mask(coding: CodingConfig, group_mask: jnp.ndarray,
                 padded_streams: int,
                 worker_major: bool = False) -> jnp.ndarray:
    """(P,) group-slot mask -> (padded_streams,) coded-stream mask.

    Divisibility-padding streams are always 0: they repeat stream 0's
    content but must never overwrite a live slot's cache."""
    if worker_major:
        per = jnp.tile(group_mask, (coding.num_workers,))
    else:
        per = jnp.repeat(group_mask, coding.num_workers)
    pad = padded_streams - per.shape[0]
    if pad:
        per = jnp.concatenate([per, jnp.zeros((pad,), per.dtype)])
    return per


def _merge_caches(old: list, new: list, stream_mask: jnp.ndarray) -> list:
    """Per-stream select between two identically-shaped cache pytrees.

    Cache leaves are (layers, streams, ...): the stream axis is axis 1
    (``transformer.init_run_caches`` stacks a leading layer axis)."""
    def merge(o, n):
        m = stream_mask.reshape((1, -1) + (1,) * (o.ndim - 2))
        return jnp.where(m > 0, n, o)
    return jax.tree.map(merge, old, new)


def _finish_pool_round(coding: CodingConfig, coded_logits: jnp.ndarray,
                       group_mask: jnp.ndarray,
                       straggler_mask: Optional[jnp.ndarray],
                       with_report: bool,
                       wshard: Optional[WorkerShardConfig] = None,
                       sample: Optional[SampleConfig] = None,
                       sample_rng: Optional[jax.Array] = None,
                       locate_quorum: Optional[jnp.ndarray] = None):
    """``_finish_round`` with the active-slot mask composed in: free
    slots' streams are excluded from the locator's verdicts (their
    garbage logits must not feed reputation) and their decoded rows are
    zeroed so stale slots can never leak a previous group's tokens.

    With ``wshard`` the round returns sampled token ids / logits from
    the sharded tail directly (row zeroing happens inside the tail,
    before on-shard sampling); without it the caller samples via
    ``_maybe_sample`` as before.
    """
    live = group_mask > 0                                  # (P,)
    if wshard is not None:
        per_query = jnp.repeat(group_mask, coding.k)       # (P*K,)
        out, (located, votes) = _finish_round_wm(
            coding, coded_logits, straggler_mask, True, wshard,
            sample, sample_rng, row_mask=per_query,
            locate_quorum=locate_quorum)
        located = jnp.logical_and(located, live[:, None])
        votes = votes * live[:, None].astype(votes.dtype)
        if with_report:
            return out, (located, votes)
        return out, None
    logits, report = _finish_round(coding, coded_logits, straggler_mask,
                                   with_report=True,
                                   locate_quorum=locate_quorum)
    located, votes = report
    located = jnp.logical_and(located, live[:, None])
    votes = votes * live[:, None].astype(votes.dtype)
    per_query = jnp.repeat(group_mask, coding.k)           # (P*K,)
    logits = logits * per_query[:, None].astype(logits.dtype)
    if with_report:
        return logits, (located, votes)
    return logits, None


def _tail_and_sample(coding: CodingConfig, coded_logits: jnp.ndarray,
                     group_mask: jnp.ndarray,
                     straggler_mask: Optional[jnp.ndarray],
                     with_report: bool,
                     wshard: Optional[WorkerShardConfig],
                     sample: Optional[SampleConfig],
                     sample_rng: Optional[jax.Array],
                     locate_quorum: Optional[jnp.ndarray]):
    """A pool round's tail (locate and decode) and its token selection,
    under the named scopes ``tail`` and ``sample`` a profile shows.  The
    worker-sharded tail samples on its shards, inside ``tail``."""
    with jax.named_scope("tail"):
        if wshard is not None:
            return _finish_pool_round(coding, coded_logits, group_mask,
                                      straggler_mask, with_report, wshard,
                                      sample, sample_rng,
                                      locate_quorum=locate_quorum)
        logits, report = _finish_pool_round(coding, coded_logits,
                                            group_mask, straggler_mask,
                                            with_report,
                                            locate_quorum=locate_quorum)
    with jax.named_scope("sample"):
        return _maybe_sample(logits, sample, sample_rng), report


def coded_pool_prefill(cfg: ModelConfig, coding: CodingConfig, params: dict,
                       state: CodedPoolState, inputs: dict, max_len: int,
                       admit_mask: jnp.ndarray,
                       straggler_mask: Optional[jnp.ndarray] = None,
                       cache_dtype=None,
                       byz_mask: Optional[jnp.ndarray] = None,
                       byz_rng: Optional[jax.Array] = None,
                       byz_sigma: float = 10.0, byz_collude: bool = False,
                       with_report: bool = False,
                       sample: Optional[SampleConfig] = None,
                       sample_rng: Optional[jax.Array] = None,
                       wshard: Optional[WorkerShardConfig] = None,
                       live_mask: Optional[jnp.ndarray] = None,
                       locate_quorum: Optional[jnp.ndarray] = None):
    """Prefill admitted group slots INTO the persistent pool.

    inputs: modality dict with leading batch = pool_groups*K query rows
    (the pool-wide prompt buffer — rows of non-admitted slots carry
    stale/padding prompts and are masked out).  ``admit_mask`` is the
    (pool_groups,) 0/1 mask of slots being admitted this round.  The
    whole pool shape prefills every call (fixed XLA shapes — this is
    what makes mid-flight admission trace-free); only admitted slots'
    caches are merged into the pool, everyone else's state is untouched.
    Returns (decoded last-token logits (pool_groups*K, V) with
    non-admitted rows zeroed — or, with ``sample``, (pool_groups*K,)
    int32 token ids sampled on device from the zeroed logits — and the
    new state); with ``with_report`` also the admit-masked (located,
    votes) locator pair.  When the caller jits this with ``state``
    donated (DESIGN.md §11), the pool caches are updated in place and
    the donated ``state`` must not be touched again after the call.
    """
    global CODED_PREFILL_TRACES
    CODED_PREFILL_TRACES += 1
    straggler_mask = _compose_live(straggler_mask, live_mask)
    admit_mask = jnp.asarray(admit_mask, jnp.float32)
    wm = wshard is not None
    with jax.named_scope("encode"):
        x = embed_inputs(cfg, params, inputs)             # (P*K, S, d)
        gk, s, d = x.shape
        g = gk // coding.k
        coded = _code_streams(coding, x.reshape(g, coding.k, s, d),
                              worker_major=wm)
    dtype = cache_dtype or jax.tree.leaves(state.caches)[0].dtype
    fresh = init_caches(cfg, coded.shape[0], max_len, dtype=dtype)
    coded_logits, fresh = prefill(cfg, params, {"embeddings": coded}, fresh)
    smask = _stream_mask(coding, admit_mask, coded.shape[0],
                         worker_major=wm)
    caches = _merge_caches(state.caches, fresh, smask)
    new_pos = jnp.where(admit_mask > 0, jnp.asarray(s, jnp.int32),
                        state.pos)
    coded_logits = _real_streams(coding, coded_logits, g)
    if byz_mask is not None and byz_rng is not None:
        coded_logits = _corrupt_logits(coding, coded_logits, byz_mask,
                                       byz_rng, byz_sigma, byz_collude,
                                       worker_major=wm)
    out, report = _tail_and_sample(coding, coded_logits, admit_mask,
                                   straggler_mask, with_report, wshard,
                                   sample, sample_rng, locate_quorum)
    new_state = CodedPoolState(caches=caches, pos=new_pos)
    if with_report:
        return out, new_state, report
    return out, new_state


def coded_pool_decode_step(cfg: ModelConfig, coding: CodingConfig,
                           params: dict, state: CodedPoolState,
                           tokens: jnp.ndarray, active_mask: jnp.ndarray,
                           straggler_mask: Optional[jnp.ndarray] = None,
                           byz_mask: Optional[jnp.ndarray] = None,
                           byz_rng: Optional[jax.Array] = None,
                           byz_sigma: float = 10.0,
                           byz_collude: bool = False,
                           with_report: bool = False,
                           sample: Optional[SampleConfig] = None,
                           sample_rng: Optional[jax.Array] = None,
                           wshard: Optional[WorkerShardConfig] = None,
                           live_mask: Optional[jnp.ndarray] = None,
                           locate_quorum: Optional[jnp.ndarray] = None):
    """One decode round over the WHOLE pool.

    tokens: (pool_groups*K, 1) int32 — the sampled next token of every
    real query row (free slots carry don't-care tokens).  All pool
    streams step every round at their own per-slot cache position
    (``decode_step`` takes the per-stream position vector); only active
    slots advance ``pos``, so a free slot harmlessly rewrites one cache
    entry until its next admission overwrites it wholesale.  Returns
    (decoded logits (pool_groups*K, V) with inactive rows zeroed — or
    sampled (pool_groups*K,) token ids with ``sample`` — and the new
    state); with ``with_report`` also the active-masked (located,
    votes).  Donation contract as in ``coded_pool_prefill``.
    """
    global CODED_DECODE_STEP_TRACES
    CODED_DECODE_STEP_TRACES += 1
    straggler_mask = _compose_live(straggler_mask, live_mask)
    from repro.models import layers as _layers
    active_mask = jnp.asarray(active_mask, jnp.float32)
    wm = wshard is not None
    with jax.named_scope("encode"):
        x = _layers.embed_tokens(cfg, params["embeddings"],
                                 tokens)                  # (P*K, 1, d)
        gk, _, d = x.shape
        g = gk // coding.k
        coded = _code_streams(coding, x.reshape(g, coding.k, 1, d),
                              worker_major=wm)
    pad = coded.shape[0] - g * coding.num_workers
    if wm:
        stream_pos = jnp.tile(state.pos, (coding.num_workers,))
    else:
        stream_pos = jnp.repeat(state.pos, coding.num_workers)
    if pad:
        # padding streams duplicate stream 0 — track its position too
        stream_pos = jnp.concatenate(
            [stream_pos, jnp.broadcast_to(stream_pos[:1], (pad,))])
    # With E == 0 the locator never reads the coded block (the decode
    # masks broadcast the straggler availability), so a free slot's
    # attention output feeds nothing but the rows `_finish_pool_round`
    # zeroes — the slot-live mask can ride into the attention kernel,
    # which then skips dead streams' KV tiles, and live rows stay
    # byte-identical.  With E > 0 the cross-group vote pool DOES read
    # every row's logits, so the free-slot garbage must stay exactly
    # what the pre-kernel program produced: live stays None there.
    stream_live = (_stream_mask(coding, active_mask, coded.shape[0],
                                worker_major=wm)
                   if coding.e == 0 else None)
    coded_logits, caches = decode_step(cfg, params, state.caches,
                                       {"embeddings": coded}, stream_pos,
                                       live=stream_live)
    coded_logits = _real_streams(coding, coded_logits, g)
    if byz_mask is not None and byz_rng is not None:
        coded_logits = _corrupt_logits(coding, coded_logits, byz_mask,
                                       byz_rng, byz_sigma, byz_collude,
                                       worker_major=wm)
    out, report = _tail_and_sample(coding, coded_logits, active_mask,
                                   straggler_mask, with_report, wshard,
                                   sample, sample_rng, locate_quorum)
    new_pos = state.pos + (active_mask > 0).astype(jnp.int32)
    new_state = CodedPoolState(caches=caches, pos=new_pos)
    if with_report:
        return out, new_state, report
    return out, new_state
