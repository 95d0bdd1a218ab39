"""The decode step writes each layer's new KV row (or SSM state) in place
into the run's stacked cache, carried through the layer loop.  It must
give bit for bit what the per-layer form gives, where each layer's
``block_decode`` runs on that layer's own slice of the cache and the new
slices are stacked back: the same operations on the same values, only
written somewhere else.  Every layer kind (dense ``A``, MoE ``M``,
Mamba-2 ``S``, shared ``G``), scalar and per-stream positions, float32
and int8 KV caches, and the layer loop unrolled (against a plain Python
loop) and rolled (against a scan over the sliced layers: XLA rounds a
loop body's fusions differently from straight-line code, so each form
is compared with the reference of the same form).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import decode_step, init_caches, init_params, layers
from repro.models import transformer

BATCH, MAX_LEN = 4, 64

# kind -> (reduced architecture, layer pattern with runs of several layers)
KINDS = {
    "A": ("qwen3-0.6b", "AAA"),
    "M": ("qwen3-moe-30b-a3b", "MMM"),
    "S": ("mamba2-780m", "SSS"),
    "G": ("zamba2-1.2b", "SSGSG"),
}


def _per_layer(cfg, params, caches, tokens, pos, live):
    """The reference: ``block_decode`` on each layer's slice of the cache,
    the new slices stacked back per run; a Python loop over the layers
    when ``cfg.unroll_scans``, else a scan with the slices as ``xs`` and
    the new ones as ``ys``."""
    x = layers.embed_tokens(cfg, params["embeddings"], tokens)
    blocks = params["blocks"]
    new_caches = []
    for (kind, count), run_p, cache in zip(
            transformer.pattern_runs(cfg.layer_pattern), blocks["runs"],
            caches):
        if kind == "G":
            kind = "A"
            run_p = jax.tree.map(lambda a: a[None], blocks["shared"])

        def layer(h, lp, one, _kind=kind):
            h, one = transformer.block_decode(
                cfg, _kind, lp, h, pos, jax.tree.map(lambda c: c[None], one),
                0, live=live)
            return h, jax.tree.map(lambda c: c[0], one)

        if cfg.unroll_scans:
            out = []
            for i in range(count):
                x, one = layer(x, *jax.tree.map(lambda a: a[i],
                                                (run_p, cache)))
                out.append(one)
            new_caches.append(jax.tree.map(lambda *ls: jnp.stack(ls), *out))
        else:
            x, nc = jax.lax.scan(lambda h, pc: layer(h, *pc), x,
                                 (run_p, cache))
            new_caches.append(nc)
    x = layers.apply_norm(cfg, params["final_norm"], x)
    logits = layers.unembed(cfg, params["embeddings"], x)[:, 0]
    return logits.astype(jnp.float32), new_caches


def _filled(caches, rng):
    """Caches filled with seeded values, so every slot a step reads or
    writes holds something distinct."""
    leaves, tree = jax.tree.flatten(caches)
    out = []
    for leaf in leaves:
        if leaf.dtype == jnp.int8:
            out.append(jnp.asarray(rng.randint(-127, 128, leaf.shape),
                                   jnp.int8))
        else:
            out.append(jnp.asarray(rng.randn(*leaf.shape), leaf.dtype))
    return jax.tree.unflatten(tree, out)


@pytest.mark.parametrize("unroll", [True, False],
                         ids=["unrolled", "rolled"])
@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
@pytest.mark.parametrize("per_stream", [False, True],
                         ids=["scalar_pos", "per_stream_pos"])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_decode_step_bitwise_equals_per_layer_loop(kind, per_stream,
                                                   kv_dtype, unroll):
    name, pattern = KINDS[kind]
    cfg = configs.get_reduced(name).with_updates(
        num_layers=len(pattern), layer_pattern=pattern,
        kv_cache_dtype="auto" if kv_dtype == "float32" else "int8",
        unroll_scans=unroll)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(7)
    caches = _filled(init_caches(cfg, BATCH, MAX_LEN), rng)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (BATCH, 1)),
                         jnp.int32)
    if per_stream:
        pos = jnp.asarray([3, 17, 0, MAX_LEN - 1], jnp.int32)
        live = jnp.asarray([1.0, 0.0, 1.0, 1.0], jnp.float32)
    else:
        pos, live = jnp.asarray(21, jnp.int32), None

    got_logits, got_caches = jax.jit(
        lambda c: decode_step(cfg, params, c, {"tokens": tokens}, pos,
                              live=live))(caches)
    ref_logits, ref_caches = jax.jit(
        lambda c: _per_layer(cfg, params, c, tokens, pos, live))(caches)

    np.testing.assert_array_equal(np.asarray(got_logits),
                                  np.asarray(ref_logits))
    assert (jax.tree.structure(got_caches)
            == jax.tree.structure(ref_caches))
    for got, ref in zip(jax.tree.leaves(got_caches),
                        jax.tree.leaves(ref_caches)):
        assert got.dtype == ref.dtype and got.shape == ref.shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    # the step wrote something: a cache that came back unchanged would
    # pass the comparison above on both sides
    assert any(not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(got_caches),
                               jax.tree.leaves(caches)))
