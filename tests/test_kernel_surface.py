"""Full-round Pallas kernel surface (DESIGN.md §16): the fused
encode->dispatch kernel and the coded-pool flash-decode kernel vs their
jnp oracles (to float rounding in interpret mode), the 128-aligned
feature tiling guard, and the KernelType-dispatched XLA paths' byte-compat
with the pre-kernel serving program."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import berrut
from repro.core.berrut import CodingConfig
from repro.kernels import berrut_matmul, flash_decode, ops, ref


def _bitwise(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# Kernel vs oracle: the interpreted kernel and the jitted oracle are two
# separate programs, so XLA may fuse, reassociate and round their float
# ops differently; they agree to a few units in the last place, not
# bitwise.  The tolerance is a few ulps of each dtype at the O(1)-O(10)
# magnitudes these operands produce.  Bitwise checks stay only where
# both sides run one program.
_ROUNDING = {jnp.dtype(jnp.float32): dict(rtol=2e-6, atol=2e-6),
             jnp.dtype(jnp.bfloat16): dict(rtol=8e-3, atol=8e-3)}


def _rounding_close(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape
    np.testing.assert_allclose(a.astype(np.float32), b.astype(np.float32),
                               **_ROUNDING[jnp.dtype(a.dtype)])


# ---------------------------------------------------- encode -> dispatch

def _encode_operands(cfg, g, f, dtype, seed=0):
    rng = np.random.RandomState(seed)
    w = berrut.encode_matrix(cfg).astype(dtype)
    x = jnp.asarray(rng.randn(g, cfg.k, f), jnp.float32).astype(dtype)
    return w, x


class TestEncodeDispatchKernelVsRef:
    """interpret-mode kernel vs the JITTED jnp oracle, to float
    rounding (separate programs)."""

    @pytest.mark.parametrize("k,s,g,f", [
        (2, 1, 1, 256),
        (4, 1, 3, 640),
        (4, 2, 2, 512),
        (8, 1, 2, 1024),
    ])
    def test_kernel_matches_jitted_ref(self, k, s, g, f):
        cfg = CodingConfig(k=k, s=s)
        w, x = _encode_operands(cfg, g, f, jnp.float32)
        got = berrut_matmul.berrut_encode_dispatch(w, x, interpret=True)
        want = jax.jit(ref.berrut_encode_dispatch_ref)(w, x)
        assert got.shape == (cfg.num_workers * g, f)
        _rounding_close(got, want)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        cfg = CodingConfig(k=4, s=1)
        w, x = _encode_operands(cfg, 2, 384, dtype)
        got = berrut_matmul.berrut_encode_dispatch(w, x, interpret=True)
        want = jax.jit(ref.berrut_encode_dispatch_ref)(w, x)
        assert got.dtype == dtype
        _rounding_close(got, want)

    @pytest.mark.parametrize("f", [200, 1000])
    def test_ragged_feature_dims(self, f):
        """Non-128-aligned F exercises the rounded-up padded tiling."""
        cfg = CodingConfig(k=4, s=1)
        w, x = _encode_operands(cfg, 2, f, jnp.float32)
        got = berrut_matmul.berrut_encode_dispatch(w, x, interpret=True)
        want = jax.jit(ref.berrut_encode_dispatch_ref)(w, x)
        _rounding_close(got, want)

    def test_matches_unfused_worker_major_composition(self):
        """The fused layout IS the pre-fused encode + swapaxes/reshape:
        stream row n*G + g must equal coded stream n of group g."""
        cfg = CodingConfig(k=4, s=1, e=1)
        g, f = 3, 512
        w, x = _encode_operands(cfg, g, f, jnp.float32)
        fused = berrut_matmul.berrut_encode_dispatch(w, x, interpret=True)
        unfused = jnp.swapaxes(
            jax.jit(ref.berrut_apply_ref)(w, x), 0, 1).reshape(-1, f)
        _rounding_close(fused, unfused)

    def test_ops_dispatch_xla_and_interpret_agree(self):
        cfg = CodingConfig(k=4, s=1)
        w, x = _encode_operands(cfg, 2, 640, jnp.float32)
        with ops.force_kernel(ops.KernelType.INTERPRET):
            a = ops.berrut_encode_dispatch(w, x)
        with ops.force_kernel(ops.KernelType.XLA):
            b = jax.jit(lambda *t: ops.berrut_encode_dispatch(*t))(w, x)
        _rounding_close(a, b)


class TestFeatureTileGuard:
    """The satellite fix: a ragged feature dim must never become one
    VMEM-busting tile — it rounds up to the next 128 multiple, clamped
    at FEATURE_TILE, and the operand is padded."""

    def test_tile_never_exceeds_feature_tile(self):
        ft = berrut_matmul.FEATURE_TILE
        for f in (1, 100, 128, 200, 512, 1000, 4096, 150_005):
            tile = berrut_matmul._feature_tile(f)
            assert tile <= ft
            assert tile % 128 == 0 or tile == f  # tiny aligned f only
            # padded length divides into whole tiles
            assert (f + (-f) % tile) % tile == 0

    def test_aligned_dims_keep_previous_tiling(self):
        assert berrut_matmul._feature_tile(512) == 512
        assert berrut_matmul._feature_tile(4096) == 512
        assert berrut_matmul._feature_tile(256) == 256

    def test_ragged_vocab_scale_is_tiled_not_monolithic(self):
        assert berrut_matmul._feature_tile(150_005) == 512

    def test_berrut_apply_ragged_matches_ref(self):
        """berrut_apply through the padded tiling still matches its
        oracle (padding columns are sliced off, F is not contracted)."""
        cfg = CodingConfig(k=4, s=1)
        w, x = _encode_operands(cfg, 2, 1000, jnp.float32)
        got = berrut_matmul.berrut_apply(w, x, interpret=True)
        want = jax.jit(ref.berrut_apply_ref)(w, x)
        _rounding_close(got, want)


# ------------------------------------------------- pool flash decode

def _pool_operands(b, h, kv, d, w, *, int8=False, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, h, d), jnp.float32)
    if int8:
        k = jnp.asarray(rng.randint(-127, 128, (b, w, kv, d)), jnp.int8)
        v = jnp.asarray(rng.randint(-127, 128, (b, w, kv, d)), jnp.int8)
    else:
        k = jnp.asarray(rng.randn(b, w, kv, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, w, kv, d), jnp.float32)
    return q, k, v


def _assert_pool_kernel_matches_ref(q, k, v, pos, live, *, softcap=0.0,
                                    kv_scale=0.0):
    got = flash_decode.pool_flash_decode(q, k, v, pos, live,
                                         softcap=softcap,
                                         kv_scale=kv_scale, interpret=True)
    want = jax.jit(functools.partial(ref.pool_decode_attention_ref,
                                     softcap=softcap,
                                     kv_scale=kv_scale))(q, k, v, pos, live)
    _rounding_close(got, want)


class TestPoolFlashDecodeVsRef:
    @pytest.mark.parametrize("h,kv", [(4, 4), (8, 4), (8, 2), (6, 1)])
    def test_gqa_head_ratios(self, h, kv):
        """MHA, GQA rep 2/4, and MQA all match the oracle."""
        b, d, w = 5, 64, 640
        q, k, v = _pool_operands(b, h, kv, d, w)
        pos = jnp.asarray([0, 3, 100, 639, 320], jnp.int32)
        _assert_pool_kernel_matches_ref(q, k, v, pos, None)

    @pytest.mark.parametrize("w", [512, 300, 1100])
    def test_ring_wrap_positions(self, w):
        """pos beyond the ring width (wrapped SWA streams) must mask to
        the full live ring, including the KV_TILE-padded tail."""
        b, h, kv, d = 4, 4, 2, 32
        q, k, v = _pool_operands(b, h, kv, d, w)
        pos = jnp.asarray([w - 1, w, 3 * w // 2, 2 * w + 7], jnp.int32)
        _assert_pool_kernel_matches_ref(q, k, v, pos, None)

    def test_mixed_per_slot_depths(self):
        """Streams admitted at different rounds sit at very different
        cache depths in the same batch (the slot-pool invariant)."""
        b, h, kv, d, w = 6, 8, 4, 64, 1024
        q, k, v = _pool_operands(b, h, kv, d, w)
        pos = jnp.asarray([0, 1, 17, 511, 512, 1023], jnp.int32)
        _assert_pool_kernel_matches_ref(q, k, v, pos, None)

    def test_masked_free_slots(self):
        """Dead slots (live == 0) output exactly zero; live slots match
        the oracle in the same batch."""
        b, h, kv, d, w = 6, 4, 2, 32, 576
        q, k, v = _pool_operands(b, h, kv, d, w)
        pos = jnp.asarray([5, 40, 100, 200, 300, 575], jnp.int32)
        live = jnp.asarray([1, 0, 1, 0, 0, 1], jnp.float32)
        _assert_pool_kernel_matches_ref(q, k, v, pos, live)
        out = flash_decode.pool_flash_decode(q, k, v, pos, live,
                                             interpret=True)
        dead = np.asarray(out)[np.asarray(live) == 0]
        np.testing.assert_array_equal(dead, np.zeros_like(dead))

    def test_softcap_and_int8_kv(self):
        b, h, kv, d, w = 4, 8, 8, 64, 300
        q, k, v = _pool_operands(b, h, kv, d, w, int8=True)
        pos = jnp.asarray([0, 100, 299, 600], jnp.int32)
        live = jnp.asarray([1, 1, 0, 1], jnp.float32)
        _assert_pool_kernel_matches_ref(q, k, v, pos, live, softcap=30.0,
                                        kv_scale=32.0)

    def test_live_rows_unaffected_by_live_mask(self):
        """Composing an all-ones live mask is a bitwise no-op, and dead
        rows never perturb live rows' outputs."""
        b, h, kv, d, w = 5, 4, 2, 32, 512
        q, k, v = _pool_operands(b, h, kv, d, w)
        pos = jnp.asarray([3, 50, 200, 400, 511], jnp.int32)
        none = flash_decode.pool_flash_decode(q, k, v, pos, None,
                                              interpret=True)
        ones = flash_decode.pool_flash_decode(
            q, k, v, pos, jnp.ones((b,), jnp.float32), interpret=True)
        _bitwise(none, ones)
        partial = flash_decode.pool_flash_decode(
            q, k, v, pos, jnp.asarray([1, 0, 1, 0, 1], jnp.float32),
            interpret=True)
        _bitwise(np.asarray(partial)[[0, 2, 4]], np.asarray(none)[[0, 2, 4]])


class TestPoolOpsDispatch:
    def test_xla_path_is_byte_compat_with_pre_kernel_program(self):
        """The XLA path of ops.pool_decode_attention must reproduce the
        pre-kernel serving program exactly: materialised positional mask
        into decode_attention_ref (the old attention_decode vector
        branch), byte for byte."""
        b, h, kv, d, w = 5, 8, 4, 64, 640
        q, k, v = _pool_operands(b, h, kv, d, w)
        pos = jnp.asarray([0, 3, 100, 639, 320], jnp.int32)

        def old_path(q, k, v, pos):
            valid = jnp.arange(w)[None, :] <= pos[:, None]
            return ref.decode_attention_ref(q, k, v, valid)

        with ops.force_kernel(ops.KernelType.XLA):
            got = jax.jit(lambda *t: ops.pool_decode_attention(*t))(
                q, k, v, pos)
            got_ones = jax.jit(
                lambda *t: ops.pool_decode_attention(*t))(
                    q, k, v, pos, jnp.ones((b,), jnp.float32))
        want = jax.jit(old_path)(q, k, v, pos)
        _bitwise(got, want)
        # an all-ones live mask composes to the same mask -> same bytes
        _bitwise(got_ones, want)

    def test_interpret_close_to_xla_path(self):
        """Cross-implementation sanity: the two paths are different
        softmax factorisations of the same math (allclose, not bitwise)."""
        b, h, kv, d, w = 4, 4, 2, 32, 576
        q, k, v = _pool_operands(b, h, kv, d, w)
        pos = jnp.asarray([5, 40, 300, 575], jnp.int32)
        with ops.force_kernel(ops.KernelType.INTERPRET):
            a = ops.pool_decode_attention(q, k, v, pos)
        with ops.force_kernel(ops.KernelType.XLA):
            b_ = ops.pool_decode_attention(q, k, v, pos)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-5, atol=2e-5)


class TestAttentionDecodeLiveThreading:
    def test_vector_branch_live_none_equals_all_ones(self):
        """attention_decode's per-stream branch: threading an all-live
        mask is bitwise identical to not threading one (the serving
        byte-compat contract for live slots)."""
        from repro.models import attention
        from repro.models.config import ModelConfig

        cfg = ModelConfig(name="t", arch_type="dense", num_layers=1,
                          d_model=64, num_heads=4, num_kv_heads=2,
                          d_ff=128, vocab_size=256)
        p = attention.init_attention(cfg, jax.random.PRNGKey(0),
                                     jnp.float32)
        rng = np.random.RandomState(3)
        bsz, w = 4, 32
        x = jnp.asarray(rng.randn(bsz, 1, cfg.d_model), jnp.float32)
        cache = {
            "k": jnp.asarray(rng.randn(bsz, w, cfg.num_kv_heads,
                                       cfg.head_dim), jnp.float32),
            "v": jnp.asarray(rng.randn(bsz, w, cfg.num_kv_heads,
                                       cfg.head_dim), jnp.float32),
        }
        pos = jnp.asarray([0, 5, 17, 31], jnp.int32)
        cache = jax.tree.map(lambda c: c[None], cache)   # one layer
        out_none, cache_none = attention.attention_decode(
            cfg, p, x, pos, cache, 0)
        out_ones, cache_ones = attention.attention_decode(
            cfg, p, x, pos, cache, 0, live=jnp.ones((bsz,), jnp.float32))
        _bitwise(out_none, out_ones)
        jax.tree.map(_bitwise, cache_none, cache_ones)
