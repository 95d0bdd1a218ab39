"""Worker-mesh coded serving tests (DESIGN.md §13).

The W>1 checks need 8 jax devices.  On a single-device host they run in
a subprocess that forces 8 virtual CPU devices via XLA_FLAGS (the local
fallback — jax pins its device count at first init); the multi-device CI
leg runs the SAME script in-process and skips the redundant subprocess.

The golden contract pinned here: with a straggler mask of exactly
``decode_quorum`` survivors, sampled tokens (greedy AND top-k) from the
worker-sharded survivor-gather path at W ∈ {4, 8} are BITWISE equal to
the single-device legacy pool path, round for round.  Raw logits are
only allclose across W (XLA re-tiles the model matmuls for sharded
shapes); the token stream is the unit of bit-reproducibility.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _device_count() -> int:
    import jax
    return len(jax.devices())


_MESH_SCRIPT = r"""
import os
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
import contextlib

import jax
import jax.numpy as jnp
import numpy as np

assert len(jax.devices()) >= 8, jax.devices()

from repro import configs
from repro.core.berrut import CodingConfig
from repro.launch import hlo_analysis
from repro.launch.mesh import make_host_mesh, make_worker_mesh
from repro.launch.worker_mesh import WorkerShardConfig
from repro.models import init_params, partitioning
from repro.serving import coded_serving
from repro.serving.continuous import ContinuousLLMExecutor
from repro.serving.sampling import SampleConfig

# --- mesh constructors ---------------------------------------------------
m1 = make_host_mesh(data=2, model=1)
assert m1.axis_names == ("data", "model")          # worker=1 keeps 2 axes
m2 = make_host_mesh(worker=4, data=2, model=1)
assert m2.axis_names == ("worker", "data", "model")
wm = make_worker_mesh(8)
assert wm.axis_names == ("worker", "model")
assert wm.devices.shape == (8, 1)
print("MESHES-OK")

cfg = configs.get_reduced("qwen3-0.6b")
params = init_params(cfg, jax.random.PRNGKey(0))
coding = CodingConfig(k=2, s=2, e=1)       # 8 coded streams, quorum 4
POOL, PLEN, STEPS = 2, 8, 3
pk = POOL * coding.k
rng = np.random.RandomState(0)
prompts = rng.randint(0, cfg.vocab_size, (pk, PLEN)).astype(np.int32)
ones_p = np.ones((POOL,), np.float32)
mask = np.zeros((coding.num_workers,), np.float32)
mask[[0, 2, 5, 7]] = 1.0                   # exactly the quorum survives


def serve(workers, wshard, sample):
    # Prefill + STEPS decode rounds; returns the stacked token stream.
    # Also asserts the executor invariants the sharded path must keep:
    # exactly one trace per jitted step and in-place donated pool state.
    with contextlib.ExitStack() as stack:
        if workers > 1:
            mesh = make_worker_mesh(workers)
            stack.enter_context(mesh)
            stack.enter_context(
                partitioning.logical_sharding_context(mesh))
        ex = ContinuousLLMExecutor(
            cfg, coding, params, pool_groups=POOL,
            max_len=PLEN + STEPS + 8, sample=sample, wshard=wshard)
        p0 = coded_serving.CODED_PREFILL_TRACES
        d0 = coded_serving.CODED_DECODE_STEP_TRACES
        state = ex.init_state()
        toks, state, _ = ex.prefill(state, prompts, ones_p, mask)
        out = [np.asarray(toks)]
        for _ in range(STEPS):
            old_leaf = jax.tree.leaves(state.caches)[0]
            toks, state, _ = ex.decode(
                state, np.asarray(toks).reshape(pk, 1), ones_p, mask)
            assert old_leaf.is_deleted(), "pool state was not donated"
            out.append(np.asarray(toks))
        assert coded_serving.CODED_PREFILL_TRACES - p0 == 1
        assert coded_serving.CODED_DECODE_STEP_TRACES - d0 == 1
    return np.stack(out)


for sample in (SampleConfig(), SampleConfig(top_k=3, temperature=0.7)):
    base = serve(1, None, sample)              # legacy single-device path
    w1 = serve(1, WorkerShardConfig(), sample)
    assert np.array_equal(base, w1), (sample, base, w1)
    for w in (4, 8):
        got = serve(w, WorkerShardConfig(), sample)
        assert np.array_equal(base, got), (sample, w, base, got)
print("TOKENS-BITWISE-OK")

# --- Pallas kernels under the mesh run per device ------------------------
# GSPMD cannot partition a Mosaic kernel: ops._per_device wraps each call
# in a shard_map over the batch / feature axis.  Interpreted kernels take
# the same wrapper, so W=4 must reproduce W=1 token for token.
from repro.kernels import ops

with ops.force_kernel("interpret"):
    w1 = serve(1, WorkerShardConfig(), SampleConfig())
    w4 = serve(4, WorkerShardConfig(), SampleConfig())
assert np.array_equal(w1, w4), (w1, w4)
# a kernel with two outputs (SSD scan: y, final state)
srng = np.random.RandomState(1)
sargs = (jnp.asarray(srng.randn(8, 64, 2, 16), jnp.float32),
         jnp.asarray(np.abs(srng.randn(8, 64, 2)) * 0.1 + 0.01, jnp.float32),
         jnp.zeros((2,), jnp.float32),
         jnp.asarray(srng.randn(8, 64, 8) * 0.5, jnp.float32),
         jnp.asarray(srng.randn(8, 64, 8) * 0.5, jnp.float32),
         jnp.ones((2,), jnp.float32))
ssd = jax.jit(lambda *a: ops.ssd(*a, chunk=32))
with ops.force_kernel("interpret"):
    want = ssd(*sargs)
    mesh = make_worker_mesh(4)
    with mesh, partitioning.logical_sharding_context(mesh):
        got = jax.jit(lambda *a: ops.ssd(*a, chunk=32))(*sargs)
for g, w_ in zip(got, want):
    np.testing.assert_allclose(np.asarray(g), np.asarray(w_), rtol=1e-5,
                               atol=1e-5)
print("PER-DEVICE-KERNELS-OK")


# --- survivor-only gather moves fewer bytes than replicated --------------
def decode_bytes(mode):
    mesh = make_worker_mesh(8)
    with mesh, partitioning.logical_sharding_context(mesh):
        ex = ContinuousLLMExecutor(
            cfg, coding, params, pool_groups=POOL, max_len=PLEN + STEPS + 8,
            sample=SampleConfig(), wshard=WorkerShardConfig(mode=mode))
        largs = (params, ex.init_state(), jnp.zeros((pk, 1), jnp.int32),
                 jnp.asarray(ones_p), jnp.asarray(mask),
                 jnp.zeros((coding.num_workers,), jnp.float32),
                 jax.random.PRNGKey(0), jnp.asarray(0.0, jnp.float32),
                 jax.random.PRNGKey(1),
                 jnp.ones((coding.num_workers,), jnp.float32),
                 jnp.asarray(0, jnp.int32))
        text = ex._decode.lower(*largs).compile().as_text()
    return hlo_analysis.collective_bytes(text)


surv = decode_bytes("survivor")
repl = decode_bytes("replicated")
assert surv["total"] < repl["total"], (surv, repl)
assert surv.get("all-gather", 0.0) < repl.get("all-gather", 0.0), \
    (surv, repl)
print("BYTES-OK")
print("WORKER-MESH-OK")
"""


@pytest.mark.skipif(_device_count() >= 8,
                    reason="in-process variant covers the multi-device leg")
def test_worker_mesh_subprocess():
    """Local fallback: the W>1 golden checks in a fresh 8-device process."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    out = subprocess.run([sys.executable, "-c", _MESH_SCRIPT],
                         capture_output=True, text=True, timeout=1200,
                         env=env)
    assert "WORKER-MESH-OK" in out.stdout, \
        out.stdout + "\n" + out.stderr[-3000:]


@pytest.mark.skipif(_device_count() < 8,
                    reason="needs >= 8 devices (multi-device CI leg)")
def test_worker_mesh_inprocess():
    """Same golden checks with real in-process collectives (CI leg)."""
    exec(compile(_MESH_SCRIPT, "<worker-mesh>", "exec"),
         {"__name__": "__worker_mesh__"})


# --- off-mesh unit tests (any device count) ------------------------------

def test_worker_shard_config_validation():
    from repro.core.berrut import CodingConfig
    from repro.launch.worker_mesh import WorkerShardConfig

    with pytest.raises(ValueError):
        WorkerShardConfig(mode="bogus")
    with pytest.raises(ValueError):
        WorkerShardConfig(gather_width=0)
    coding = CodingConfig(k=2, s=2, e=1)       # 8 workers, quorum 4
    assert WorkerShardConfig().resolved_width(coding) == 4
    assert WorkerShardConfig(gather_width=6).resolved_width(coding) == 6
    # clamped to the stream count
    assert WorkerShardConfig(gather_width=99).resolved_width(coding) == 8


def test_validate_layout_off_mesh():
    from repro.core.berrut import CodingConfig
    from repro.launch.worker_mesh import (WorkerShardConfig,
                                          validate_layout,
                                          worker_axis_size)

    wshard = WorkerShardConfig()
    assert worker_axis_size(wshard) == 1       # no active mesh
    assert validate_layout(CodingConfig(k=2, s=2, e=1), wshard) == 1


def test_survivor_slots_compaction():
    import jax.numpy as jnp

    from repro.launch.worker_mesh import _survivor_slots

    avail = jnp.asarray([1.0, 0.0, 1.0, 1.0, 0.0, 1.0, 0.0, 0.0])
    slots, idx, valid = _survivor_slots(avail, 4)
    # survivors 0,2,3,5 compact (order-preserving) into slots 0..3;
    # non-survivors land in the spill row (== width)
    assert slots.tolist() == [0, 4, 1, 2, 4, 3, 4, 4]
    assert idx.tolist() == [0, 2, 3, 5]
    assert valid.tolist() == [1.0, 1.0, 1.0, 1.0]

    one = jnp.zeros((8,)).at[1].set(1.0)
    slots, idx, valid = _survivor_slots(one, 4)
    assert slots.tolist()[1] == 0              # the lone survivor -> slot 0
    assert idx.tolist()[0] == 1
    assert valid.tolist() == [1.0, 0.0, 0.0, 0.0]


def test_off_mesh_wshard_matches_legacy_decode():
    """Degenerate W=1 survivor compaction == legacy masked decode when
    exactly the quorum survives (the compaction-exactness invariant)."""
    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.core.berrut import CodingConfig
    from repro.launch.worker_mesh import WorkerShardConfig
    from repro.models import init_params
    from repro.serving.coded_serving import coded_prefill

    cfg = configs.get_reduced("qwen3-0.6b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    coding = CodingConfig(k=2, s=2, e=1)
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, cfg.vocab_size, (4, 8)).astype(np.int32)
    mask = np.zeros((coding.num_workers,), np.float32)
    mask[[0, 2, 5, 7]] = 1.0                   # exactly quorum survivors
    legacy, _ = coded_prefill(cfg, coding, params, {"tokens": tokens},
                              max_len=16, straggler_mask=jnp.asarray(mask))
    sharded, _ = coded_prefill(cfg, coding, params, {"tokens": tokens},
                               max_len=16, straggler_mask=jnp.asarray(mask),
                               wshard=WorkerShardConfig())
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(legacy),
                               atol=2e-4, rtol=2e-4)


def test_scheduler_rejects_narrow_gather_width():
    """A pool waiting for more responses than the gather width must fail
    loudly at construction, not silently truncate survivors."""
    import jax

    from repro import configs
    from repro.core.berrut import CodingConfig
    from repro.launch.worker_mesh import WorkerShardConfig
    from repro.models import init_params
    from repro.serving.continuous import (ContinuousConfig,
                                          ContinuousLLMExecutor,
                                          ContinuousScheduler)
    from repro.serving.latency import LatencyModel

    cfg = configs.get_reduced("qwen3-0.6b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    coding = CodingConfig(k=2, s=2, e=1)       # quorum 4 of 8
    executor = ContinuousLLMExecutor(cfg, coding, params, pool_groups=2,
                                     max_len=16,
                                     wshard=WorkerShardConfig())
    with pytest.raises(ValueError, match="gather width"):
        ContinuousScheduler(
            ContinuousConfig(coding=coding, pool_groups=2, wait_for=6),
            LatencyModel(), executor)
    # an explicit gather_width covering the wait bound is accepted
    wide = ContinuousLLMExecutor(cfg, coding, params, pool_groups=2,
                                 max_len=16,
                                 wshard=WorkerShardConfig(gather_width=6))
    ContinuousScheduler(
        ContinuousConfig(coding=coding, pool_groups=2, wait_for=6),
        LatencyModel(), wide)
