"""Trace reduction, work counts and peaks of the chip benchmark, checked
on a small synthetic trace and hand counts at small shapes (CPU)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import model  # noqa: E402
import peaks  # noqa: E402
import spec  # noqa: E402
import trace_reduce as tr  # noqa: E402
import work  # noqa: E402


# op names as the chip's trace shows them (first trace, by hand)
POOL = ('%pool_flash_decode.7 = f32[20,8,2,128] custom-call(s32[20] '
        '%reshape.291), custom_call_target="tpu_custom_call"')
TAIL = ('%fused_group_decode.1 = f32[4,4,151936] custom-call(...), '
        'custom_call_target="tpu_custom_call"')


def _trace():
    # device 0: ops at [0,10) [5,20) [30,40) [70,80); host spans below
    ops = [tr.Op("%fusion.1 = f32[8] fusion(...)", 0, 10, 0),
           tr.Op(POOL, 5, 20, 0),
           tr.Op("%fusion.1 = f32[8] fusion(...)", 30, 40, 0),
           tr.Op(TAIL, 70, 80, 0),
           tr.Op("%while.4 = (s32[]) while(...)", 0, 60, 1),
           tr.Op("%fusion.9 = f32[2] fusion(...)", 0, 50, 1)]
    spans = [tr.Span("bench.decode", 0, 28),
             tr.Span("bench.pacing", 40, 60),
             tr.Span("bench.prefill", 65, 100)]
    return tr.Trace(ops=ops, spans=spans)


def test_union_merges_overlaps_and_clips():
    assert tr.union([(5, 20), (0, 10), (30, 40)], 0, 35) == [(0, 20),
                                                             (30, 35)]
    assert tr.union([(0, 5)], 10, 20) == []


def test_busy_and_idle_gaps():
    t = _trace()
    lo, hi = t.window()
    assert (lo, hi) == (0, 100)
    assert tr.busy_ns(t, 0, lo, hi) == 20 + 10 + 10
    assert tr.idle_gaps(t, 0, lo, hi) == [(20, 30), (40, 70), (80, 100)]
    assert tr.busy_ns(t, 1, lo, hi) == 60
    assert t.devices == [0, 1]


def test_gaps_are_labelled_by_host_span():
    t = _trace()
    assert tr.label((20, 30), t.spans) == "decode call"
    assert tr.label((40, 70), t.spans) == "pacing wait"
    assert tr.label((80, 100), t.spans) == "prefill call"
    assert tr.label((61, 63), t.spans) == "scheduler"
    got = tr.longest_gaps(t, 0, 0, 100)
    assert got == [("pacing wait", 30e-9), ("prefill call", 20e-9),
                   ("decode call", 10e-9)]


def test_kernel_time_by_name():
    t = _trace()
    pool = spec.metric_reader("pool_flash_decode_roofline")
    tail = spec.metric_reader("fused_group_decode_roofline")
    pool_kernel = pool.__globals__["is_kernel"]
    tail_kernel = tail.__globals__["is_kernel"]
    assert tr.op_seconds(t, pool_kernel) == pytest.approx(15e-9)
    assert tr.op_seconds(t, tail_kernel) == pytest.approx(10e-9)
    # a loop op holds others and is left out of the top list
    assert tr.top_ops(t, 2) == [("%fusion.9 = f32[2] fusion(...)", 50e-9),
                                ("%fusion.1 = f32[8] fusion(...)", 20e-9)]


DENSE = spec.arch_module("qwen3_dense")
DIMS = DENSE.Dims(layers=2, hidden=8, heads=4, kv_heads=2, head_dim=2,
                  ffn=16, vocab=10, rope_theta=1e4, eps=1e-6,
                  embed_mult=1.0, dtype="float32")


def test_params_met_in_products():
    # per layer: q 8x4x2 + o 4x2x8 = 128, k,v 8x2x2 x2 = 64, mlp 3x8x16
    assert DENSE.matmul_params(DIMS) == 2 * (128 + 64 + 384) + 10 * 8


def test_pool_attention_counts():
    # one group of 3 live streams at 5 keys, one of 3 at 1 key
    flops, bytes_ = work.pool_attention(DIMS, [(3, 5), (3, 1)])
    assert flops == 3 * 4 * 4 * 2 * 5 + 3 * 4 * 4 * 2 * 1
    per = lambda keys: 2 * keys * 2 * 2 * 4 + 2 * 4 * 2 * 4  # noqa: E731
    assert bytes_ == 3 * per(5) + 3 * per(1)


def test_tail_counts():
    flops, bytes_ = work.tail(k=4, workers=5, vocab=10, live_groups=2)
    assert flops == 2 * 4 * 5 * 10 * 2
    assert bytes_ == 4 * 10 * (5 + 4) * 2


def test_token_and_prompt_flops():
    assert (work.token_flops(DENSE, DIMS, 0)
            == 2 * DENSE.matmul_params(DIMS) + 2 * 32)
    assert work.prompt_flops(DENSE, DIMS, 3) == pytest.approx(
        sum(work.token_flops(DENSE, DIMS, d) for d in range(3)))


def test_roofline_bound():
    assert work.roofline_s(10.0, 1.0, 10.0, 10.0) == (1.0, "compute")
    assert work.roofline_s(1.0, 20.0, 10.0, 10.0) == (2.0, "memory")


def test_peaks_known_and_unknown():
    p = peaks.peaks("TPU v5 lite")
    assert p["flops_bf16"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_coding_workers_and_quorum():
    assert model.Coding(4, 1, 0).workers == 5
    assert model.Coding(4, 1, 0).quorum == 4
    assert model.Coding(4, 1, 1).workers == 11
    assert model.Coding(4, 1, 1).quorum == 6
