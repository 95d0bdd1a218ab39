"""Distribution tests: logical-axis resolution, mesh construction, and a
reduced-scale lower+compile of every step kind on a multi-device host mesh
(the in-tests mirror of the production dry-run, deliverable e)."""

import os


# Must run in a subprocess-isolated module: jax device count locks on
# first init.  pytest-forked isn't available, so we use 8 devices for the
# whole test session via conftest-free env guard: these tests only run
# when the env var is set (the Makefile target / CI invokes them), OR we
# spawn a subprocess here.
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SUBPROC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.configs.shapes import ShapeConfig, serving_coding
from repro.launch import shardings, specs
from repro.models import logical_axes, partitioning, cache_axes
from repro.models.partitioning import resolve_spec, padded_batch
from repro.optim import OptimizerConfig, opt_state_axes
from repro.training import TrainConfig, train_step
from repro.serving.coded_serving import (CodedServingState,
                                         coded_decode_step, coded_prefill)

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)

# --- resolve_spec unit checks ------------------------------------------
spec = resolve_spec(mesh, ("fsdp", "heads"), shape=(128, 8))
assert spec == P("data", "model"), spec
# non-divisible head count falls back to replicated
spec = resolve_spec(mesh, ("fsdp", "kv_heads"), shape=(128, 3))
assert spec == P("data", None), spec
# batch padding helper
with partitioning.logical_sharding_context(mesh):
    assert padded_batch(5) == 8 and padded_batch(8) == 8

# --- train step lower+compile on 3 arch families ------------------------
for arch in ("qwen3-0.6b", "qwen3-moe-30b-a3b", "mamba2-780m",
             "zamba2-1.2b"):
    cfg = configs.get_reduced(arch).with_updates(remat=True)
    shape = ShapeConfig("t", 64, 8, "train")
    with mesh, partitioning.logical_sharding_context(mesh):
        params_s, opt_s = specs.model_state_specs(cfg)
        batch_s = specs.train_batch_specs(cfg, shape)
        ax = logical_axes(cfg)
        jitted = jax.jit(
            lambda p, o, b, _c=cfg: train_step(_c, TrainConfig(), p, o, b),
            in_shardings=(shardings.tree_shardings(mesh, ax, params_s),
                          shardings.tree_shardings(
                              mesh, opt_state_axes(ax), opt_s),
                          shardings.batch_tree_shardings(mesh, batch_s)))
        compiled = jitted.lower(params_s, opt_s, batch_s).compile()
        assert compiled.cost_analysis() is not None
    print(f"train-compile OK {arch}")

# --- coded decode step with padding (8 streams % 4 != 0 case) -----------
cfg = configs.get_reduced("qwen3-0.6b")
shape = ShapeConfig("d", 128, 8, "decode")
coding = serving_coding(shape, 4, 1, 0)   # K=4,S=1 -> 2 groups x 5 = 10
with mesh, partitioning.logical_sharding_context(mesh):
    state_s, tokens_s = specs.decode_state_specs(cfg, shape, coding)
    # stream count (dim 1; dim 0 is the layer-stack axis) must be padded
    # to a multiple of 4 (data axis): 2 groups x 5 workers = 10 -> 12
    assert state_s.caches[0]["k"].shape[1] == 12
    params_s, _ = specs.model_state_specs(cfg)
    ax = logical_axes(cfg)
    jitted = jax.jit(
        lambda p, st, t: coded_decode_step(cfg, coding, p, st, t),
        in_shardings=(
            shardings.tree_shardings(mesh, ax, params_s),
            CodedServingState(
                caches=shardings.cache_shardings(mesh, cfg, state_s.caches),
                pos=shardings.replicated(mesh)),
            shardings.batch_tree_shardings(mesh, tokens_s)))
    compiled = jitted.lower(params_s, state_s, tokens_s).compile()
print("decode-compile OK")

# --- collective parser sees loop scaling --------------------------------
from repro.launch import hlo_analysis
txt = compiled.as_text()
c1 = hlo_analysis.collective_bytes(txt, loop_factor=1.0)
c2 = hlo_analysis.collective_bytes(txt, loop_factor=7.0)
assert c2["total"] >= c1["total"]

# --- batch_sharding fallbacks (worker/pod/data axis prefix) -------------
from repro.launch.mesh import make_host_mesh, make_worker_mesh
sh = shardings.batch_sharding(mesh, 2, 8)       # 8 % 4 == 0
assert sh.spec == P("data", None), sh.spec
sh = shardings.batch_sharding(mesh, 2, 6)       # 6 % 4 != 0 -> replicate
assert sh.spec == P(None, None), sh.spec
sh = shardings.batch_sharding(mesh, 2, 1)       # batch=1 (long_500k)
assert sh.spec == P(None, None), sh.spec
wmesh = make_host_mesh(worker=4, data=2, model=1)
sh = shardings.batch_sharding(wmesh, 3, 16)     # 16 % (4*2) == 0
assert sh.spec == P(("worker", "data"), None, None), sh.spec
sh = shardings.batch_sharding(wmesh, 2, 2)      # drops "worker", keeps data
assert sh.spec == P("data", None), sh.spec
wmesh2 = make_worker_mesh(8)
assert wmesh2.axis_names == ("worker", "model")
assert wmesh2.devices.shape == (8, 1)
print("BATCH-SHARDING-OK")
print("ALL-OK")
"""


def _device_count() -> int:
    import jax
    return len(jax.devices())


@pytest.mark.skipif(_device_count() >= 8,
                    reason="in-process variant covers the multi-device leg")
def test_sharded_lowering_subprocess():
    """End-to-end distribution check in a fresh 8-device process (the
    local fallback — jax pins its device count at first init)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", _SUBPROC_SCRIPT],
                         capture_output=True, text=True, timeout=1200,
                         env=env)
    assert "ALL-OK" in out.stdout, out.stdout + "\n" + out.stderr[-3000:]


@pytest.mark.skipif(_device_count() < 8,
                    reason="needs >= 8 devices (multi-device CI leg)")
def test_sharded_lowering_inprocess():
    """Same distribution checks without process isolation (CI leg)."""
    exec(compile(_SUBPROC_SCRIPT, "<sharded-lowering>", "exec"),
         {"__name__": "__sharded_lowering__"})


def test_multihost_single_process_helpers():
    """make_array_from_process_local_data degenerates to identity with
    one process; worker-rank ownership is the whole pool."""
    import jax
    from repro.launch import multihost

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    batch = {"tokens": np.arange(12, dtype=np.int32).reshape(4, 3)}
    out = multihost.global_batch_from_host_shard(mesh, batch)
    np.testing.assert_array_equal(np.asarray(out["tokens"]),
                                  batch["tokens"])
    assert multihost.host_worker_ranks(mesh) == [0]

    wmesh = jax.make_mesh((1, 1), ("worker", "model"))
    assert multihost.host_worker_ranks(wmesh) == [0]
    pool = {"k": np.random.RandomState(0).randn(8, 2, 3)
            .astype(np.float32)}
    gout = multihost.global_pool_from_host_shard(wmesh, pool)
    np.testing.assert_array_equal(np.asarray(gout["k"]), pool["k"])


def test_dryrun_merges_existing_xla_flags():
    """launch.dryrun must never clobber a caller-set device count
    (regression: it used to overwrite XLA_FLAGS unconditionally)."""
    script = "\n".join([
        "import os",
        "os.environ['XLA_FLAGS'] = ("
        "'--xla_force_host_platform_device_count=8 --xla_foo=1')",
        "from repro.launch.dryrun import merge_device_count_flag",
        "assert os.environ['XLA_FLAGS'] == ("
        "'--xla_force_host_platform_device_count=8 --xla_foo=1'), "
        "os.environ['XLA_FLAGS']",
        "assert merge_device_count_flag('', 512) == ("
        "'--xla_force_host_platform_device_count=512')",
        "assert merge_device_count_flag('--a', 4) == ("
        "'--a --xla_force_host_platform_device_count=4')",
        "print('DRYRUN-FLAGS-OK')",
    ])
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=600,
                         env=env)
    assert "DRYRUN-FLAGS-OK" in out.stdout, \
        out.stdout + "\n" + out.stderr[-3000:]


def test_mesh_constants():
    from repro.launch.mesh import (HBM_BW, ICI_BW_PER_LINK,
                                   PEAK_FLOPS_BF16)
    assert PEAK_FLOPS_BF16 == 197e12
    assert HBM_BW == 819e9
    assert ICI_BW_PER_LINK == 50e9


def test_hlo_collective_formulas():
    """Ring-cost accounting matches hand-computed values."""
    from repro.launch.hlo_analysis import collective_bytes
    txt = """
ENTRY %main (p: f32[16]) -> f32[16] {
  %ag = f32[16]{0} all-gather(%p), replica_groups=[2,4]<=[8], dimensions={0}
  %ar = f32[16]{0} all-reduce(%ag), replica_groups=[2,4]<=[8], to_apply=%add
}
"""
    out = collective_bytes(txt)
    assert out["all-gather"] == 64 * 3 / 4          # B(n-1)/n, n=4
    assert out["all-reduce"] == 2 * 64 * 3 / 4
