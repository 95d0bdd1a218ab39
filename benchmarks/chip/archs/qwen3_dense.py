"""Qwen3 dense decoder, the model of a configuration whose ``arch`` is
``qwen3_dense`` (Hugging Face ``Qwen3ForCausalLM``).

Every layer is grouped-query attention with per-head q/k RMSNorm and
rotary embeddings, then a SwiGLU MLP; the embedding table is tied to the
unembedding.  The weights are laid out as the program's dense decoder
takes them (one run of stacked layers), and the reference's layer stack
is plain ``jax.numpy`` over the helpers of ``reference.py``.  What each
function is for is in ``spec.arch_module``.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import NUMERICS, matmul, rms_norm, rope, rotary
from weights import EMBED_STD, norm_scale, normal


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    rope_theta: float
    eps: float
    embed_mult: float             # token embedding scale (see departures)
    dtype: str                    # the dtype the configuration serves


def dims(config: dict) -> Dims:
    dep = config.get("departures", {})
    return Dims(
        layers=int(config["num_hidden_layers"]),
        hidden=int(config["hidden_size"]),
        heads=int(config["num_attention_heads"]),
        kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        ffn=int(config["intermediate_size"]),
        vocab=int(config["vocab_size"]),
        rope_theta=float(config["rope_theta"]),
        eps=float(config["rms_norm_eps"]),
        embed_mult=(math.sqrt(config["hidden_size"])
                    if dep.get("embedding_times_sqrt_hidden") else 1.0),
        dtype=config["torch_dtype"])


def matmul_params(d: Dims) -> int:
    """Parameters a token meets in matrix products: every layer's
    projections and MLP, plus the unembedding (the tied table)."""
    h, kv, hd, f = d.heads, d.kv_heads, d.head_dim, d.ffn
    per_layer = (d.hidden * h * hd * 2 + d.hidden * kv * hd * 2
                 + 3 * d.hidden * f)
    return d.layers * per_layer + d.vocab * d.hidden


def program_model(config: dict):
    """The program's ModelConfig for the configuration file."""
    from repro import configs
    dt = config["torch_dtype"]
    return configs.get_config(config["registry_name"]).with_updates(
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"], rope_theta=config["rope_theta"],
        norm_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"],
        param_dtype=dt, activation_dtype=dt)


def reduced(config: dict) -> dict:
    """The configuration at the program's reduced size of its registry
    entry, for CPU rehearsals."""
    from repro import configs
    r = configs.get_reduced(config["registry_name"])
    return dict(config, num_hidden_layers=r.num_layers,
                hidden_size=r.d_model, num_attention_heads=r.num_heads,
                num_key_value_heads=r.num_kv_heads, head_dim=r.head_dim,
                intermediate_size=r.d_ff, vocab_size=r.vocab_size)


def init(d: Dims, key) -> dict:
    n, h, kv, hd, f, v = (d.layers, d.heads, d.kv_heads, d.head_dim, d.ffn,
                          d.vocab)
    ks = iter(jax.random.split(key, 16))
    return {
        "embeddings": {"embed": normal(next(ks), (v, d.hidden), EMBED_STD)},
        "blocks": {"runs": [{
            "norm1": {"scale": norm_scale(next(ks), (n, d.hidden))},
            "attn": {
                "wq": normal(next(ks), (n, d.hidden, h, hd), d.hidden ** -0.5),
                "wk": normal(next(ks), (n, d.hidden, kv, hd),
                             d.hidden ** -0.5),
                "wv": normal(next(ks), (n, d.hidden, kv, hd),
                             d.hidden ** -0.5),
                "wo": normal(next(ks), (n, h, hd, d.hidden), (h * hd) ** -0.5),
                "q_norm": norm_scale(next(ks), (n, hd)),
                "k_norm": norm_scale(next(ks), (n, hd)),
            },
            "norm2": {"scale": norm_scale(next(ks), (n, d.hidden))},
            "mlp": {
                "w_gate": normal(next(ks), (n, d.hidden, f), d.hidden ** -0.5),
                "w_in": normal(next(ks), (n, d.hidden, f), d.hidden ** -0.5),
                "w_out": normal(next(ks), (n, f, d.hidden), f ** -0.5),
            },
        }]},
        "final_norm": {"scale": norm_scale(next(ks), (d.hidden,))},
    }


def embedding(params: dict):
    """(V, d) input table."""
    return params["embeddings"]["embed"]


def unembedding(params: dict):
    """(V, d) output table: the input table, tied."""
    return params["embeddings"]["embed"]


def _kv(d: Dims, p: dict, a, cos, sin, numerics: str):
    """One layer's cached keys (q/k-normed, rotated) and values of its
    normed input ``a`` (S, T, d)."""
    store, w_prec, _ = NUMERICS[numerics]
    cdt = jnp.dtype(store)
    k = matmul("btd,dhk->bthk", a, p["attn"]["wk"], cdt, w_prec).astype(cdt)
    v = matmul("btd,dhk->bthk", a, p["attn"]["wv"], cdt, w_prec).astype(cdt)
    k = rope(rms_norm(k, p["attn"]["k_norm"], d.eps, cdt), cos, sin, cdt)
    return k, v


def hidden_states(d: Dims, params: dict, x: jnp.ndarray,
                  numerics: str) -> jnp.ndarray:
    """(S, T, d) input embeddings -> (S, T, d) final-normed states."""
    store, w_prec, a_prec = NUMERICS[numerics]
    cdt = jnp.dtype(store)
    run = jax.tree.map(lambda a: a.astype(cdt), params["blocks"]["runs"][0])
    t = x.shape[1]
    cos, sin = rotary(d, t)
    causal = jnp.tril(jnp.ones((t, t), bool))
    rep = d.heads // d.kv_heads

    def mm(eq, a, b, precision=w_prec):
        return matmul(eq, a, b, cdt, precision).astype(cdt)

    def layer(h, p):
        a = rms_norm(h, p["norm1"]["scale"], d.eps, cdt)
        q = mm("btd,dhk->bthk", a, p["attn"]["wq"])
        q = rope(rms_norm(q, p["attn"]["q_norm"], d.eps, cdt), cos, sin, cdt)
        k, v = _kv(d, p, a, cos, sin, numerics)
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
        s = matmul("bthk,buhk->bhtu", q, k, cdt, a_prec)
        s = jnp.where(causal, s / np.sqrt(d.head_dim), -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1).astype(cdt)
        o = mm("bhtu,buhk->bthk", pr, v, a_prec)
        h = h + mm("bthk,hkd->btd", o, p["attn"]["wo"])
        m = rms_norm(h, p["norm2"]["scale"], d.eps, cdt)
        g = (jax.nn.silu(mm("btd,df->btf", m, p["mlp"]["w_gate"]))
             * mm("btd,df->btf", m, p["mlp"]["w_in"]))
        return h + mm("btf,fd->btd", g, p["mlp"]["w_out"]), None

    h, _ = jax.lax.scan(layer, x.astype(cdt), run)
    return rms_norm(h, params["final_norm"]["scale"], d.eps, cdt)


def first_kv(d: Dims, params: dict, x: jnp.ndarray, numerics: str):
    """The first attention layer's cached keys and values, each (S, T,
    kv_heads, head_dim) in the numerics' storage dtype, of the coded
    input embeddings ``x`` (S, T, d)."""
    store = jnp.dtype(NUMERICS[numerics][0])
    p = jax.tree.map(lambda a: a[0].astype(store),
                     params["blocks"]["runs"][0])
    cos, sin = rotary(d, x.shape[1])
    a = rms_norm(x, p["norm1"]["scale"], d.eps, store)
    return _kv(d, p, a, cos, sin, numerics)


def program_first_kv(state, streams: slice, positions: int) -> tuple:
    """The program's first attention cache in its pool state, run 0 and
    layer 0: (keys, values) of the coded ``streams`` at their first
    ``positions`` positions."""
    cache = state.caches[0]
    return tuple(cache[name][0, streams, :positions] for name in ("k", "v"))
