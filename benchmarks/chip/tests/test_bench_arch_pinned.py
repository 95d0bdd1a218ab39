"""The benchmark's model, now the architecture module
``archs/qwen3_dense.py``, computes bitwise what the benchmark computed
before it had architecture modules.

At the program's reduced Qwen3 size on the CPU, for a fixed seed and
fixed inputs: the weights, the reference's decoded logits and its first
attention layer's cache (in the numerics the configuration states and
in the bfloat16 control), and the work counts at the reduced and the
published size.  The values were recorded from commit ac7d8a2, where
``weights.init``, ``reference.py`` and ``model.Dims`` held the dense
Qwen3 layout; a digest is of an array's bytes, so equal means bitwise
equal.
"""

import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import reference  # noqa: E402
import spec  # noqa: E402
import weights  # noqa: E402
import work  # noqa: E402
from model import Coding  # noqa: E402

SEED = 1234
CONFIG = "qwen3-0.6b.e0"

LEAVES = {
    "['blocks']['runs'][0]['attn']['k_norm']":
        "float32[2, 64]:9a47c48157b86cb7",
    "['blocks']['runs'][0]['attn']['q_norm']":
        "float32[2, 64]:aba5ee47f8a22e44",
    "['blocks']['runs'][0]['attn']['wk']":
        "float32[2, 256, 2, 64]:b8aa2f0898c29f86",
    "['blocks']['runs'][0]['attn']['wo']":
        "float32[2, 4, 64, 256]:e878f84d698ebb3f",
    "['blocks']['runs'][0]['attn']['wq']":
        "float32[2, 256, 4, 64]:4f4e8ee1737550a2",
    "['blocks']['runs'][0]['attn']['wv']":
        "float32[2, 256, 2, 64]:7e08b20751258366",
    "['blocks']['runs'][0]['mlp']['w_gate']":
        "float32[2, 256, 512]:2ae16200aedc2428",
    "['blocks']['runs'][0]['mlp']['w_in']":
        "float32[2, 256, 512]:5c42ca0b38452274",
    "['blocks']['runs'][0]['mlp']['w_out']":
        "float32[2, 512, 256]:24be8fb628e5ce91",
    "['blocks']['runs'][0]['norm1']['scale']":
        "float32[2, 256]:618bc10871b5ba8f",
    "['blocks']['runs'][0]['norm2']['scale']":
        "float32[2, 256]:7716e1ec1a98431d",
    "['embeddings']['embed']": "float32[512, 256]:f08edd0a03415af0",
    "['final_norm']['scale']": "float32[256]:8f9360a76ad5a247",
}
LOGITS = {"float32": "float32[4, 16, 512]:e3700e927214afd6",
          "bfloat16": "float32[4, 16, 512]:720a6809cc5c593b"}
FIRST_KV = {"float32": ("float32[5, 32, 2, 64]:dc468f17e88d47c2",
                        "float32[5, 32, 2, 64]:dba6fadd41eb9c46"),
            "bfloat16": ("float32[5, 32, 2, 64]:bb870b7014e40841",
                         "float32[5, 32, 2, 64]:f6f9780b1e35f812")}
# (reduced, published) x depth 0, 127, 1000 / prompt 16, 128, 512
TOKEN_FLOPS = [2623488.0, 2883584.0, 4671488.0,
               1192198144.0, 1221328896.0, 1421574144.0]
PROMPT_FLOPS = [42221568.0, 352452608.0, 1611137024.0,
                19102695424.0, 154465730560.0, 640411500544.0]


def digest(x) -> str:
    a = np.asarray(x)
    return (f"{a.dtype}{list(a.shape)}:"
            + hashlib.sha256(a.tobytes()).hexdigest()[:16])


@pytest.fixture(scope="module")
def model():
    """(arch, reduced dims, published dims, weights from SEED)."""
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    config = spec.load_json(spec.ROOT / entry["file"])
    arch = spec.arch_module(config["arch"])
    dims = arch.dims(arch.reduced(config))
    return arch, dims, arch.dims(config), weights.make(arch, dims, SEED)


def test_weights_are_pinned(model):
    params = model[3]
    got = {jax.tree_util.keystr(p): digest(leaf)
           for p, leaf in jax.tree_util.tree_leaves_with_path(params)}
    assert got == LEAVES


@pytest.mark.parametrize("numerics", ["float32", "bfloat16"])
def test_reference_is_pinned(model, numerics):
    arch, dims, _, params = model
    c = Coding(4, 1, 0)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, dims.vocab, (4, 32)).astype(np.int32))
    enc = jnp.asarray(reference.encode_matrix(c), jnp.float32)
    used = rng.rand(16, c.workers) > 0.3
    used[:, :c.k] = True
    dec = jnp.asarray(np.stack([reference.decode_matrix(c, u)
                                for u in used]), jnp.float32)
    pos = jnp.arange(8, 24, dtype=jnp.int32)
    states = reference._states(arch, dims, params, tokens, enc, numerics)
    logits = reference._decoded(arch, params, states, pos, dec, numerics)
    assert digest(logits) == LOGITS[numerics]
    k, v = reference.first_layer_kv(arch, dims, params, tokens, enc,
                                    numerics)
    assert (digest(k), digest(v)) == FIRST_KV[numerics]


def test_work_counts_are_pinned(model):
    arch, dims, full, _ = model
    assert [work.token_flops(arch, d, x) for d in (dims, full)
            for x in (0, 127, 1000)] == TOKEN_FLOPS
    assert [work.prompt_flops(arch, d, x) for d in (dims, full)
            for x in (16, 128, 512)] == PROMPT_FLOPS
