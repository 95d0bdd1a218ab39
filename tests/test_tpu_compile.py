"""Ahead-of-time TPU compiles of the main-path Pallas kernels at
qwen3-0.6b serving widths (G=4 groups, K=4, N+1=5, V=151936, 20 pool
streams, 16/8 heads of 128, a 2048-slot cache), against a described
``v5e:2x2`` topology: no chip is attached, nothing runs.

Interpret mode accepts block shapes the TPU's Mosaic lowering refuses
(the last two block dims must be (8, 128)-aligned or whole) and never
checks VMEM; these compiles do.  The topology is described inside a
module-scoped fixture, never at import: only one process at a time may
load the TPU library, and every test worker imports this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import berrut_decode, berrut_matmul, flash_attention
from repro.kernels import flash_decode, ops
from repro.models import partitioning

G, K, N1, V = 4, 4, 5, 151936
B, H, KV, D = G * N1, 16, 8, 128
PROMPT, CACHE, D_MODEL = 16, 2048, 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topology = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topology
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _pool_decode(q, k, v, pos, live):
    return flash_decode.pool_flash_decode(q, k, v, pos, live)


# name -> (kernel call, [(shape, dtype)] of its arguments)
CASES = {
    "flash_attention": (
        flash_attention.flash_attention,
        [((B, PROMPT, H, D), jnp.float32), ((B, PROMPT, KV, D), jnp.float32),
         ((B, PROMPT, KV, D), jnp.float32)]),
    "pool_flash_decode": (
        _pool_decode,
        [((B, H, D), jnp.float32), ((B, CACHE, KV, D), jnp.float32),
         ((B, CACHE, KV, D), jnp.float32), ((B,), jnp.int32),
         ((B,), jnp.float32)]),
    "flash_decode": (
        flash_decode.flash_decode,
        [((B, H, D), jnp.float32), ((B, CACHE, KV, D), jnp.float32),
         ((B, CACHE, KV, D), jnp.float32), ((B, CACHE), jnp.bool_)]),
    "fused_group_decode_shared_mask": (
        berrut_decode.fused_group_decode,
        [((G, N1, V), jnp.float32), ((N1,), jnp.float32),
         ((K,), jnp.float32), ((N1,), jnp.float32)]),
    "fused_group_decode_per_group_masks": (
        berrut_decode.fused_group_decode,
        [((G, N1, V), jnp.float32), ((G, N1), jnp.float32),
         ((K,), jnp.float32), ((N1,), jnp.float32)]),
    "berrut_encode_dispatch": (
        berrut_matmul.berrut_encode_dispatch,
        [((N1, K), jnp.float32), ((G, K, PROMPT * D_MODEL), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, arg_shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in arg_shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert held < 16e9            # one v5e chip's HBM


# ops under a 4-way "worker" mesh: GSPMD cannot partition a Mosaic
# kernel ("Mosaic kernels cannot be automatically partitioned"), so the
# ops run each call per device (ops._per_device).  The pool at K=4, S=4
# has 8 coded streams per group, split over the "worker" axis; the
# encode's operands stay whole on every device.
STREAMS = G * 8
F32, I32 = jnp.float32, jnp.int32
MESH_CASES = {
    "attention": (
        lambda q, k, v: ops.attention(q, k, v),
        [((STREAMS, PROMPT, H, D), F32, "worker"),
         ((STREAMS, PROMPT, KV, D), F32, "worker"),
         ((STREAMS, PROMPT, KV, D), F32, "worker")]),
    "pool_decode_attention": (
        lambda q, k, v, pos, live: ops.pool_decode_attention(
            q, k, v, pos, live),
        [((STREAMS, H, D), F32, "worker"),
         ((STREAMS, CACHE, KV, D), F32, "worker"),
         ((STREAMS, CACHE, KV, D), F32, "worker"),
         ((STREAMS,), I32, "worker"), ((STREAMS,), F32, "worker")]),
    "berrut_encode_dispatch": (
        ops.berrut_encode_dispatch,
        [((8, K), F32, None), ((G, K, D_MODEL), F32, None)]),
}


@pytest.mark.parametrize("name", sorted(MESH_CASES))
def test_op_compiles_on_worker_mesh(name, topo):
    mesh = Mesh(np.array(topo.devices[:4]).reshape(4, 1),
                ("worker", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    fn, arg_specs = MESH_CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dtype,
                                 sharding=NamedSharding(mesh, P(axis)))
            for shape, dtype, axis in arg_specs]
    with ops.force_kernel("pallas"), \
            partitioning.logical_sharding_context(mesh):
        compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _compiled_pool_decode_step(one_chip, coding, max_len):
    """The served decode-step program at qwen3-0.6b's published width,
    pool of G groups, compiled for one described chip."""
    from repro import configs
    from repro.models import init_params
    from repro.serving import ContinuousLLMExecutor, SampleConfig

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    cfg = configs.get_config("qwen3-0.6b")
    n1 = coding.num_workers
    params = on_chip(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    ex = ContinuousLLMExecutor(cfg, coding, params, pool_groups=G,
                               max_len=max_len, sample=SampleConfig())

    def arg(shape, dtype=F32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    key = arg((2,), jnp.uint32)
    args = (params, on_chip(jax.eval_shape(ex.init_state)),
            arg((G * K, 1), I32), arg((G,)), arg((n1,)), arg((n1,)), key,
            arg(()), key, arg((n1,)), arg((), I32))
    with ops.force_kernel("pallas"):
        return ex._decode.lower(*args).compile()


def test_pool_decode_step_compiles_at_full_width(one_chip):
    """The served E=1 decode-step program (encode, 28 layers with pool
    attention, locate, fused decode, sampling) at qwen3-0.6b's published
    width fits one chip, with its three kernels in place."""
    from repro.core.berrut import CodingConfig

    compiled = _compiled_pool_decode_step(
        one_chip, CodingConfig(k=K, s=1, e=1), PROMPT + 10)
    assert compiled.as_text().count("tpu_custom_call") >= 3
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert held < 16e9            # one v5e chip's HBM


def test_pool_decode_step_writes_the_pool_in_place(one_chip):
    """The E=0 decode step at long_gen's shape (G=4, max_len 1154, K=4,
    S=1) holds the donated KV pool once: each layer's new row is written
    in place into the stacked pool, so no copy and no
    dynamic-update-slice produces a whole stacked cache, and the
    program's scratch is under the bytes of one of them (K or V)."""
    from repro.core.berrut import CodingConfig

    coding = CodingConfig(k=K, s=1, e=0)
    max_len = 1154
    compiled = _compiled_pool_decode_step(one_chip, coding, max_len)
    pool = (28, G * coding.num_workers, max_len, KV, D)
    whole_pool = re.compile(
        r"= f32\[%s\]\{[^}]*\} (copy|dynamic-update-slice)\("
        % ",".join(map(str, pool)))
    assert whole_pool.findall(compiled.as_text()) == []
    cache_bytes = 4 * int(np.prod(pool))
    assert compiled.memory_analysis().temp_size_in_bytes < cache_bytes
