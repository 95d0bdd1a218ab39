"""Dispatching wrappers around the Pallas kernels.

Model code calls these; the implementation is selected by a uniform
``KernelType`` (the mamba-jax kernel-interface idiom):

  * ``KernelType.PALLAS``    -> pl.pallas_call kernels (kernels/*.py)
  * ``KernelType.XLA``       -> the pure-jnp reference oracles (ref.py)
  * ``KernelType.INTERPRET`` -> the kernel bodies under the Pallas
    interpreter on CPU (bit-identity tests)

``kernel_type()`` resolves the active type: the ``FORCE_KERNEL``
override wins (tests and the dry-run pin it — the dry-run lowers for a
512-device CPU mesh where TPU kernels cannot lower), else PALLAS on TPU
backends and XLA everywhere else; a backend that fails to start raises
instead of falling back.  ``force_kernel(...)`` scopes an override; enum
members or their string names ("pallas" / "xla" / "jnp" / "interpret")
both coerce.  Every kernel keeps an oracle that INTERPRET output matches
to float rounding.
"""

from __future__ import annotations

import contextlib
import enum
import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro.kernels import ref


class KernelType(enum.Enum):
    """Which implementation of a kernel op runs: the compiled Pallas
    kernel, the XLA reference oracle, or the kernel body interpreted on
    CPU (how tests pin bit-identity without a TPU)."""

    PALLAS = "pallas"
    XLA = "xla"
    INTERPRET = "interpret"

    @classmethod
    def coerce(cls, value: Union["KernelType", str]) -> "KernelType":
        if isinstance(value, cls):
            return value
        try:
            return _KERNEL_TYPE_NAMES[str(value).lower()]
        except KeyError:
            raise ValueError(
                f"unknown kernel type {value!r}; expected one of "
                f"{sorted(_KERNEL_TYPE_NAMES)}") from None


# "jnp" stays accepted as an alias of the XLA reference path (the name
# the pre-enum string dispatch used).
_KERNEL_TYPE_NAMES = {
    "pallas": KernelType.PALLAS,
    "xla": KernelType.XLA,
    "jnp": KernelType.XLA,
    "interpret": KernelType.INTERPRET,
}

# Global dispatch override; prefer the force_kernel() context manager.
FORCE_KERNEL: Optional[KernelType] = None

# Cached jax.devices() platform lookup: every op invocation used to call
# jax.devices() (which grabs a lock and builds the device list) just to
# re-learn the backend.  The platform cannot change within a process, so
# resolve it once; FORCE_KERNEL keeps its override semantics because it
# is consulted BEFORE the cache on every call (tests flip it at runtime).
_PLATFORM: Optional[str] = None


def kernel_type() -> KernelType:
    """The KernelType every op dispatches on for the current call."""
    global _PLATFORM
    if FORCE_KERNEL is not None:
        return KernelType.coerce(FORCE_KERNEL)
    if _PLATFORM is None:
        # no fallback: a backend that fails to start raises here rather
        # than silently serving the XLA reference path
        _PLATFORM = jax.devices()[0].platform
    return KernelType.PALLAS if _PLATFORM == "tpu" else KernelType.XLA


@contextlib.contextmanager
def force_kernel(kind: Optional[Union[KernelType, str]]):
    """Scope a dispatch override (None clears any active override)."""
    global FORCE_KERNEL
    prev = FORCE_KERNEL
    FORCE_KERNEL = None if kind is None else KernelType.coerce(kind)
    try:
        yield
    finally:
        FORCE_KERNEL = prev


def _kernel_args() -> Optional[dict]:
    """None -> run the XLA oracle; else the kwargs for the kernel call."""
    kt = kernel_type()
    if kt is KernelType.XLA:
        return None
    return {"interpret": kt is KernelType.INTERPRET}


def _per_device(kernel, args, logical, out_logical):
    """Run a kernel call under the active mesh.

    GSPMD cannot partition a Mosaic kernel, so under a multi-device
    ``logical_sharding_context`` the call runs per device inside a
    ``shard_map`` that splits each argument along its logical axes
    (``logical``: one tuple per argument; ``out_logical``: a matching
    pytree for the outputs).  An axis that does not divide its mesh axes
    is replicated, so only independent axes (batch, features) may be
    named.  Off-mesh, or already inside a ``shard_map`` body, the kernel
    runs as is.
    """
    from repro.models import partitioning
    mesh = partitioning.active_mesh()
    if (mesh is None or mesh.size == 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        return kernel(*args)

    def spec(a, axes):
        return partitioning.resolve_spec(mesh, axes, shape=a.shape)

    out = jax.eval_shape(kernel, *args)
    out_specs = jax.tree.map(spec, out, out_logical)
    return jax.shard_map(
        kernel, mesh=mesh,
        in_specs=tuple(spec(a, ax) for a, ax in zip(args, logical)),
        out_specs=out_specs, check_vma=False)(*args)


def _features(x) -> tuple:
    """Logical axes of a coding operand: only the feature axis splits."""
    return (None,) * (x.ndim - 1) + ("coded_flat",)


def _batch(x) -> tuple:
    return ("batch",) + (None,) * (x.ndim - 1)


def berrut_apply(weights: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    kw = _kernel_args()
    if kw is not None:
        from repro.kernels import berrut_matmul
        return _per_device(
            functools.partial(berrut_matmul.berrut_apply, **kw),
            (weights, x), ((None, None), _features(x)), _features(x))
    return ref.berrut_apply_ref(weights, x)


def berrut_encode_dispatch(weights: jnp.ndarray,
                           x: jnp.ndarray) -> jnp.ndarray:
    """One-pass encode -> worker-major dispatch: (O, I) @ (G, I, F) ->
    (O*G, F) flat streams in the ``n*G + g`` order the "worker" mesh
    axis shards (DESIGN.md §13) — the encode contraction and the stream
    layout move fused into one HBM pass."""
    kw = _kernel_args()
    if kw is not None:
        from repro.kernels import berrut_matmul
        return _per_device(
            functools.partial(berrut_matmul.berrut_encode_dispatch, **kw),
            (weights, x), ((None, None), _features(x)),
            (None, "coded_flat"))
    return ref.berrut_encode_dispatch_ref(weights, x)


def fused_group_decode(grouped: jnp.ndarray, masks: jnp.ndarray,
                      alphas: jnp.ndarray, betas: jnp.ndarray, *,
                      c_vote: int = 0):
    """Fused coded-round tail: per-group decode-matrix construction +
    (G, N+1, V) -> (G, K, V) contraction (+ the locator's strided
    vote-coordinate gather when ``c_vote > 0``) in one pass over the
    coded-logit block.  masks: (N+1,) shared or (G, N+1) per-group.
    """
    kw = _kernel_args()
    if kw is not None:
        from repro.kernels import berrut_decode
        # the vote gather reads strided vocab columns: keep V whole then
        vocab = (None, None, "coded_flat" if c_vote <= 0 else None)
        return _per_device(
            functools.partial(berrut_decode.fused_group_decode,
                              c_vote=c_vote, **kw),
            (grouped, masks, alphas, betas),
            (vocab, (None,) * masks.ndim, (None,), (None,)),
            vocab if c_vote <= 0 else (vocab, (None, None, None)))
    return ref.fused_group_decode_ref(grouped, masks, alphas, betas,
                                      c_vote=c_vote)


# XLA-path attention implementation: "naive" materialises (S, L) scores;
# "blocked" is the flash-style online-softmax scan (§Perf optimisation).
# "auto" picks blocked for long sequences.
ATTN_IMPL = "auto"
BLOCKED_THRESHOLD = 8192


def attention(q, k, v, *, causal=True, window=None, prefix=0, softcap=0.0,
              q_offset=0, unroll=False):
    kw = _kernel_args()
    if kw is not None:
        from repro.kernels import flash_attention
        return _per_device(
            functools.partial(
                flash_attention.flash_attention, causal=causal,
                window=window, prefix=prefix, softcap=softcap,
                q_offset=q_offset, **kw),
            (q, k, v), (_batch(q), _batch(k), _batch(v)), _batch(q))
    use_blocked = (ATTN_IMPL == "blocked"
                   or (ATTN_IMPL == "auto"
                       and k.shape[1] >= BLOCKED_THRESHOLD))
    if use_blocked:
        return ref.attention_blocked(q, k, v, causal=causal, window=window,
                                     prefix=prefix, softcap=softcap,
                                     q_offset=q_offset, unroll=unroll)
    return ref.attention_ref(q, k, v, causal=causal, window=window,
                             prefix=prefix, softcap=softcap,
                             q_offset=q_offset)


def decode_attention(q, k_cache, v_cache, kv_mask, *, softcap=0.0,
                     kv_scale=0.0):
    """kv_scale > 0 marks int8 caches (values quantised as round(x*scale)).

    The Pallas kernel dequantises per block in VMEM (HBM traffic = int8
    bytes); the jnp path dequantises up front (XLA materialises the copy —
    the proxy-vs-target divergence recorded in EXPERIMENTS.md §5.3).
    """
    kw = _kernel_args()
    if kw is not None:
        from repro.kernels import flash_decode
        args = (q, k_cache, v_cache, kv_mask)
        return _per_device(
            functools.partial(flash_decode.flash_decode, softcap=softcap,
                              kv_scale=kv_scale, **kw),
            args, tuple(_batch(a) for a in args), _batch(q))
    if kv_scale > 0.0:
        k_cache = k_cache.astype(jnp.float32) / kv_scale
        v_cache = v_cache.astype(jnp.float32) / kv_scale
    return ref.decode_attention_ref(q, k_cache.astype(q.dtype),
                                    v_cache.astype(q.dtype), kv_mask,
                                    softcap=softcap)


def pool_decode_attention(q, k_cache, v_cache, pos, live=None, *,
                          softcap=0.0, kv_scale=0.0):
    """Slot-pool decode attention: per-stream (B,) ring positions and an
    optional (B,) slot-live mask instead of a materialised (B, W) mask.

    The Pallas kernel derives every KV tile's validity in-kernel from the
    SMEM-resident scalars (``kvpos <= pos`` composed with ``live``) — no
    full-width masked score block.  The XLA path keeps the pre-kernel
    program byte-for-byte: it builds the positional mask exactly as
    ``models.attention.attention_decode`` used to and runs
    ``decode_attention_ref`` (for a live row the composed mask equals the
    positional mask, so threading ``live`` changes nothing on live rows;
    an all-dead row is garbage on both paths — uniform-softmax garbage
    here, zeros in the kernel — and callers must mask it downstream).
    """
    kw = _kernel_args()
    if kw is not None:
        from repro.kernels import flash_decode
        kernel = functools.partial(flash_decode.pool_flash_decode,
                                   softcap=softcap, kv_scale=kv_scale, **kw)
        args = (q, k_cache, v_cache, jnp.asarray(pos, jnp.int32))
        if live is not None:
            args += (live,)
        return _per_device(kernel, args, tuple(_batch(a) for a in args),
                           _batch(q))
    w = k_cache.shape[1]
    valid = jnp.arange(w)[None, :] <= jnp.asarray(pos, jnp.int32)[:, None]
    if live is not None:
        valid = jnp.logical_and(valid, (live > 0)[:, None])
    if kv_scale > 0.0:
        k_cache = k_cache.astype(jnp.float32) / kv_scale
        v_cache = v_cache.astype(jnp.float32) / kv_scale
    return ref.decode_attention_ref(q, k_cache.astype(q.dtype),
                                    v_cache.astype(q.dtype), valid,
                                    softcap=softcap)


def ssd(x, dt, a_log, b, c, d_skip, h0=None, chunk: int = 128):
    kw = _kernel_args()
    if kw is not None:
        from repro.kernels import ssd_scan
        args = (x, dt, a_log, b, c, d_skip) + (() if h0 is None else (h0,))
        logical = (_batch(x), _batch(dt), (None,), _batch(b), _batch(c),
                   (None,)) + (() if h0 is None else (_batch(h0),))
        return _per_device(
            functools.partial(ssd_scan.ssd_chunked, chunk=chunk, **kw),
            args, logical, (_batch(x), ("batch", None, None, None)))
    return ref.ssd_chunked_ref(x, dt, a_log, b, c, d_skip, h0=h0, chunk=chunk)


def ssd_step(h, x_t, dt_t, a_log, b_t, c_t, d_skip):
    # Single-token state update: elementwise + tiny einsum — XLA fuses this
    # fine on every backend; no kernel needed.
    return ref.ssd_step_ref(h, x_t, dt_t, a_log, b_t, c_t, d_skip)
