"""CPU rehearsal of the chip benchmark: each configuration's first cell
at the program's reduced size through the harness functions, and the
lookup of architectures, configurations, traffic mixes, cells and
metrics by name."""

import dataclasses
import hashlib
import json
import math
import time

import numpy as np
import pytest

import reduced_cell as rc

import harness  # noqa: E402
import spec  # noqa: E402
import traffic  # noqa: E402
import window  # noqa: E402

SEED = 2 ** 31 + 12345          # past 32 signed bits, as the driver's are


def first_cells() -> list:
    """The first cell of each configuration in BENCHMARK.json."""
    first = {}
    for w in spec.load_json(spec.ROOT / "BENCHMARK.json")["workloads"]:
        first.setdefault(w["config"], w["name"])
    return list(first.values())


@pytest.fixture(scope="module")
def cell():
    return rc.short_cell("qwen3-0.6b.e0.long_gen")


@pytest.mark.parametrize("workload", first_cells())
@pytest.mark.parametrize("trace", [False, True])
def test_last_line_keys(workload, trace):
    """A run's result line, each configuration at the size its
    architecture module's ``reduced`` gives."""
    cell = rc.short_cell(workload)
    with rc.no_compile_cache():
        out = harness.run_cell(cell, SEED, 3.0, trace, [rc.StandInTPU()],
                               time.perf_counter(), log=lambda s: None)
    json.dumps(out)
    assert list(out)[:3] == ["correct", "attempted", "failed"]
    assert list(out)[-1] == "checks"
    assert set(out) <= {"correct", "attempted", "failed", "metrics",
                        "device", "breakdown", "checks"}
    assert out["correct"] is True and out["attempted"] > 0
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"}
    names = {m.name for m in (cell.per_layer if trace else cell.end_to_end)}
    # device-trace readers find nothing off the chip and stay silent
    assert set(out["metrics"]) <= names
    if not trace:
        assert set(out["metrics"]) == names
    for m in out["metrics"].values():
        assert math.isfinite(m["value"]) and m["value"] > 0


def test_window_arithmetic(cell):
    seconds = 2.0
    with rc.no_compile_cache():
        seed_of = harness.seeds(SEED)
        dims, coding, _, executor, scheduler = harness.build(cell, seed_of)
        harness.warm_up(executor, scheduler, 16, coding.k, dims.vocab)
        requests = traffic.generate(cell.traffic, None, seconds, dims.vocab,
                                    seed_of["traffic"])
        sched, clock, t_start, t_end = harness.run_window(
            executor, scheduler, requests, seconds)
    served, runs = window.rebuild(sched.trace, sched.groups, executor.calls,
                                  coding.k, t_start)
    assert t_end - t_start >= seconds
    # the window ends at the first round boundary after its length
    assert clock["boundaries"][-1] < t_start + seconds <= t_end
    assert all(t_start <= c.t0 <= c.t1 <= t_end for c in executor.calls)
    tokens = sum(len(s.tokens) for s in served.values())
    assert tokens == window.tokens_served(served) > 0
    gaps = window.itls_ms(served)
    assert gaps.size == sum(max(len(s.times) - 1, 0)
                            for s in served.values())
    assert (gaps > 0).all()
    for s in served.values():
        assert s.times[0] >= s.due_s          # never served before due
        assert len(s.tokens) <= s.budget
        if s.finished:
            np.testing.assert_array_equal(s.tokens, sched.results[s.uid])
    due = {i: t_start + a / 1e3 for i, a in enumerate(requests.arrival_ms)}
    ttft = window.ttfts_ms(due, served, t_end)
    assert ttft.size == len(requests.arrival_ms)    # a backlog: all due
    assert (ttft > 0).all()


def test_same_work_for_every_seed():
    mix = spec.load_json(spec.HERE / "traffic" / "short_chat.json")
    a = traffic.generate(mix, 10.0, 30.0, 1000, 1)
    b = traffic.generate(mix, 10.0, 30.0, 1000, 2 ** 31 + 7)
    np.testing.assert_array_equal(a.budgets, b.budgets)
    np.testing.assert_array_equal(a.arrival_ms, b.arrival_ms)
    assert not np.array_equal(a.prompts, b.prompts)
    assert a.budgets.min() >= 8 and a.budgets.max() <= 128
    # every block of BLOCK requests holds each quantile once
    block = np.sort(a.budgets[:traffic.BLOCK])
    np.testing.assert_array_equal(
        block, np.sort(a.budgets[traffic.BLOCK:2 * traffic.BLOCK]))
    # budgets and gaps are not drawn in the same order
    gaps = np.diff(a.arrival_ms, prepend=0.0)
    assert not np.array_equal(np.argsort(a.budgets[:traffic.BLOCK]),
                              np.argsort(gaps[:traffic.BLOCK]))


UNTIED_ARCH = '''"""qwen3_dense with an untied output head."""

import jax

import spec
import weights

dense = spec.arch_module("qwen3_dense")
Dims, dims, matmul_params, reduced = (dense.Dims, dense.dims,
                                      dense.matmul_params, dense.reduced)
hidden_states, first_kv, embedding, program_first_kv = (
    dense.hidden_states, dense.first_kv, dense.embedding,
    dense.program_first_kv)


def program_model(config):
    return dense.program_model(config).with_updates(tie_embeddings=False)


def init(d, key):
    key, head = jax.random.split(key)
    tree = dense.init(d, key)
    tree["embeddings"]["lm_head"] = weights.normal(
        head, (d.hidden, d.vocab), d.hidden ** -0.5)
    return tree


def unembedding(params):
    return params["embeddings"]["lm_head"].T
'''


def _digests(root) -> dict:
    return {str(p): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_new_files_are_found_by_name(tmp_path):
    """An architecture, configuration, mix, arrival process, cell and
    metric added as files with a BENCHMARK.json entry, and no edit to any
    existing file.  The architecture, a module that reuses
    ``qwen3_dense`` with an untied head, runs through the harness at the
    program's reduced size and comes out correct."""
    existing = _digests(spec.HERE)
    bench_file = spec.ROOT / "BENCHMARK.json"
    bench_before = bench_file.read_bytes()
    for d in ("archs", "configs", "traffic", "arrivals", "cells",
              "metrics"):
        (tmp_path / d).mkdir()
    (tmp_path / "archs" / "qwen3_untied.py").write_text(UNTIED_ARCH)
    config = spec.load_json(spec.HERE / "configs" / "qwen3-0.6b.e0.json")
    (tmp_path / "configs" / "new-model.json").write_text(json.dumps(
        dict(config, num_hidden_layers=3, arch="qwen3_untied",
             tie_word_embeddings=False)))
    (tmp_path / "traffic" / "new_mix.json").write_text(
        json.dumps(dict(rc.SHORT_TRAFFIC, arrivals="every_second")))
    (tmp_path / "arrivals" / "every_second.py").write_text(
        "import numpy as np\n\n\n"
        "def count(mix, rate_rps, seconds):\n    return 10\n\n\n"
        "def times_ms(mix, rate_rps, q):\n"
        "    return 1e3 * np.arange(len(q))\n")
    # four groups, as the rehearsal's own cell: with two, both can be
    # admitted in the round the window closes, leaving no cache to compare
    (tmp_path / "cells" / "new-model.new_mix.json").write_text(json.dumps(
        {"pool_groups": 4, "rate_rps": None,
         "limits": {"max_logit_gap": 0.25, "kv_rel_err": 5e-4,
                    "min_tokens_compared": 50}}))
    (tmp_path / "metrics" / "new.metric.py").write_text(
        "def read(ctx):\n    return ctx.answer\n")
    bench = {
        "configs": [{"name": "new-model",
                     "file": "configs/new-model.json"}],
        "workloads": [{"name": "new-model.new_mix", "config": "new-model",
                       "traffic": "new_mix", "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s", "better": "lower",
                        "source": "host_clock"}],
        "per_layer": [{"name": "new.metric", "unit": "%", "better": "higher",
                       "source": "host_clock", "layer": "x",
                       "moves": "setup_s"}]}
    c = spec.load_cell("new-model.new_mix", bench, root=tmp_path,
                       here=tmp_path)
    assert c.config["num_hidden_layers"] == 3
    assert c.cell["pool_groups"] == 4
    assert [m.name for m in c.per_layer] == ["new.metric"]
    read = spec.metric_reader("new.metric", tmp_path / "metrics")
    assert read(type("Ctx", (), {"answer": 42.0})) == 42.0
    got = traffic.generate(c.traffic, None, 1.0, 100, 3,
                           arrivals_dir=tmp_path / "arrivals")
    assert got.prompts.shape == (traffic.BLOCK, 16)
    np.testing.assert_array_equal(got.arrival_ms,
                                  1e3 * np.arange(traffic.BLOCK))

    assert c.arch.program_model(c.config).tie_embeddings is False
    # the run's traffic is a backlog: the harness finds arrival processes
    # in its own directory
    run = dataclasses.replace(c, config=c.arch.reduced(c.config),
                              traffic=rc.SHORT_TRAFFIC)
    with rc.no_compile_cache():
        out = harness.run_cell(run, SEED, 3.0, False, [rc.StandInTPU()],
                               time.perf_counter(), log=lambda s: None)
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["kv_groups_min"]["value"] >= 1
    assert _digests(spec.HERE) == existing
    assert bench_file.read_bytes() == bench_before


def test_configuration_without_arch_is_refused(tmp_path):
    (tmp_path / "configs").mkdir()
    config = spec.load_json(spec.HERE / "configs" / "qwen3-0.6b.e0.json")
    config.pop("arch")
    (tmp_path / "configs" / "old.json").write_text(json.dumps(config))
    bench = {"configs": [{"name": "old", "file": "configs/old.json"}],
             "workloads": [{"name": "old.long_gen", "config": "old",
                            "traffic": "long_gen", "chips": 1}],
             "end_to_end": [], "per_layer": []}
    with pytest.raises(KeyError, match="'arch'"):
        spec.load_cell("old.long_gen", bench, root=tmp_path)
