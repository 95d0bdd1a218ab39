"""Smoke run of the coded slot-pool serving path on a TPU.

Drives the normal serving path once, in this one process, at the full
published width of qwen3-0.6b (28 layers, d_model 1024, 16/8 heads of
128, d_ff 3072, vocab 151936) with random float32 weights made from
``--seed``, and checks what comes out:

  ContinuousScheduler -> ContinuousLLMExecutor -> coded pool prefill /
  decode step -> fused locate/decode tail -> on-device sampling,

with the Pallas kernels live.

  python chip_smoke.py               # one chip: K=4 S=1 at E=0, then at
                                     # E=1 under a persistent attacker
  python chip_smoke.py --four-chips  # the continuous pool on a 4-way
                                     # "worker" mesh vs the same trace at W=1

It fails, and never falls back to another backend, when the first JAX
device is not a TPU.  Every phase raises on a failed check, so the
process exits non-zero before it prints a result; the last line of
standard output is then ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "qwen3-0.6b"
POOL_GROUPS = 4          # group slots: 4 x (N+1) coded streams
REQUESTS = 12
PROMPT_LEN = 16
MAX_NEW = 8              # per-request budgets are drawn from 1..MAX_NEW
BYZ_SIGMA = 50.0
# Pallas vs XLA on one coded round, relative to the largest |logit|.  The
# two programs differ only in the kernels (attention, encode, decode
# tail), which round differently: on the TPU, XLA multiplies float32
# operands in one bfloat16 pass by default (about 2^-8 relative per
# product), and a random-weight 28-layer model carries the difference
# into the logits, a few 1e-3 of the logit scale on a TPU v5e.  A wrong
# mask, head, tile or decode weight is an O(1) relative error.
LOGIT_RTOL = 2e-2


class CompileCounter:
    """Counts XLA backend compiles (a persistent-cache hit is not one)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0

    def __call__(self, event: str, duration: float, **kwargs) -> None:
        if event == self.EVENT:
            self.n += 1


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def check(cond, msg: str) -> None:
    if not cond:
        fail(msg)


def count_kernels(hlo_text: str) -> int:
    return hlo_text.count("tpu_custom_call")


def make_requests(vocab: int, seed: int):
    import numpy as np
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, vocab, (PROMPT_LEN,)).astype(np.int32)
               for _ in range(REQUESTS)]
    budgets = rng.randint(1, MAX_NEW + 1, size=REQUESTS)
    return prompts, budgets


def example_args(executor, prefill: bool):
    """Concrete arguments of the executor's jitted prefill / decode
    programs (the shapes a serving round passes)."""
    import jax
    import jax.numpy as jnp
    n1 = executor.coding.num_workers
    rows = executor.pool_groups * executor.coding.k
    tokens = jnp.zeros((rows, PROMPT_LEN if prefill else 1), jnp.int32)
    return (executor.params, executor.init_state(), tokens,
            jnp.ones((executor.pool_groups,), jnp.float32),
            jnp.ones((n1,), jnp.float32), jnp.zeros((n1,), jnp.float32),
            jax.random.PRNGKey(0), jnp.asarray(0.0, jnp.float32),
            jax.random.PRNGKey(1), jnp.ones((n1,), jnp.float32),
            jnp.asarray(0, jnp.int32))


def serve(cfg, params, coding, compiles: CompileCounter, *, seed: int,
          adversary=None, wshard=None):
    """Compile the executor's two programs, then one continuous-pool
    serving run; returns (scheduler, seconds, backend compiles in it)."""
    from repro.serving import (ContinuousConfig, ContinuousLLMExecutor,
                               ContinuousScheduler, LatencyModel,
                               SampleConfig)
    executor = ContinuousLLMExecutor(
        cfg, coding, params, pool_groups=POOL_GROUPS,
        max_len=PROMPT_LEN + MAX_NEW + 2, sample=SampleConfig(),
        sample_seed=seed, wshard=wshard)
    before = compiles.n
    t0 = time.perf_counter()
    executor._prefill.lower(*example_args(executor, True)).compile()
    t1 = time.perf_counter()
    decode = executor._decode.lower(*example_args(executor, False)).compile()
    t2 = time.perf_counter()
    n_kernels = count_kernels(decode.as_text())
    print(f"  compile s: prefill {t1 - t0:.1f}, decode step {t2 - t1:.1f} "
          f"({compiles.n - before} backend compiles; a persistent-cache "
          f"hit is none); tpu_custom_call in decode-step HLO: {n_kernels}")
    check(n_kernels > 0, "the compiled decode step holds no Pallas kernel "
                         "(tpu_custom_call)")
    mem = decode.memory_analysis()
    if mem is not None:
        print(f"  decode-step memory: arguments "
              f"{mem.argument_size_in_bytes / 2**30:.2f} GiB, temp "
              f"{mem.temp_size_in_bytes / 2**30:.2f} GiB")
    sched = ContinuousScheduler(
        ContinuousConfig(coding=coding, pool_groups=POOL_GROUPS,
                         flush_deadline_ms=5.0, seed=seed,
                         adversary=adversary, max_new_tokens=MAX_NEW),
        LatencyModel(), executor)
    prompts, budgets = make_requests(cfg.vocab_size, seed)
    before = compiles.n
    t0 = time.perf_counter()
    sched.run(prompts, rate_rps=2000.0, max_new_tokens=budgets)
    seconds = time.perf_counter() - t0
    in_run = compiles.n - before
    check(len(sched.results) == REQUESTS,
          f"served {len(sched.results)} of {REQUESTS} requests")
    for uid, budget in zip(sorted(sched.results), budgets):
        toks = sched.results[uid]
        check(len(toks) == budget,
              f"request {uid}: {len(toks)} tokens, budget {budget}")
        check(((toks >= 0) & (toks < cfg.vocab_size)).all(),
              f"request {uid}: token id out of the vocab: {toks}")
    return sched, seconds, in_run


def report(label, sched, seconds, compiles: int) -> None:
    m = sched.metrics
    tokens = sum(len(t) for t in sched.results.values())
    print(f"  {label}: served {len(sched.results)} requests, {tokens} "
          f"tokens in {sched.rounds_run} pool rounds, {seconds:.2f} s on "
          f"the host clock with {compiles} backend compiles "
          f"inside it")
    if m.locate_rounds:
        print(f"  {label}: byzantine {m.attacked_rounds}/{m.locate_rounds} "
              f"rounds attacked, precision {m.detection_precision():.2f} "
              f"recall {m.detection_recall():.2f} corrupted-decode rate "
              f"{m.corrupted_decode_rate():.3f}")


def round_logits(cfg, params, coding, seed: int, kernel):
    """Decoded logits of one pool prefill round and one decode round,
    with worker 1 corrupting its coded logits in both, under the given
    kernel override (None = the platform's default dispatch)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops
    from repro.serving.coded_serving import (coded_pool_decode_step,
                                             coded_pool_prefill,
                                             init_pool_state)
    max_len = PROMPT_LEN + MAX_NEW + 2
    rows = POOL_GROUPS * coding.k
    rng = np.random.RandomState(seed)
    prompts = jnp.asarray(rng.randint(0, cfg.vocab_size, (rows, PROMPT_LEN)),
                          jnp.int32)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (rows, 1)),
                         jnp.int32)
    ones = jnp.ones((POOL_GROUPS,), jnp.float32)
    avail = jnp.ones((coding.num_workers,), jnp.float32)
    byz = jnp.zeros((coding.num_workers,), jnp.float32).at[1].set(1.0)
    key = jax.random.PRNGKey(seed)
    with ops.force_kernel(kernel):
        # fresh jit objects: the kernel choice is read while tracing
        prefill = jax.jit(lambda p, st, t: coded_pool_prefill(
            cfg, coding, p, st, {"tokens": t}, max_len, ones,
            straggler_mask=avail, byz_mask=byz, byz_rng=key,
            byz_sigma=BYZ_SIGMA, with_report=True))
        decode = jax.jit(lambda p, st, t: coded_pool_decode_step(
            cfg, coding, p, st, t, ones, straggler_mask=avail,
            byz_mask=byz, byz_rng=key, byz_sigma=BYZ_SIGMA,
            with_report=True))
        state = init_pool_state(cfg, coding, POOL_GROUPS, max_len)
        pre, state, (pre_loc, _) = prefill(params, state, prompts)
        dec, state, (dec_loc, _) = decode(params, state, tokens)
    return [np.asarray(x) for x in (pre, dec, pre_loc, dec_loc)]


def compare_kernels(cfg, params, coding, seed: int, kernel=None) -> None:
    """The platform's kernels (``kernel`` None) vs the XLA reference on
    one prefill and one decode round."""
    from repro.kernels import ops
    import numpy as np
    got = round_logits(cfg, params, coding, seed, kernel)
    want = round_logits(cfg, params, coding, seed, ops.KernelType.XLA)
    for name, a, b in (("prefill", got[0], want[0]),
                       ("decode", got[1], want[1])):
        check(np.isfinite(a).all(), f"{name} logits not finite (Pallas)")
        scale = float(np.abs(b).max())
        diff = float(np.abs(a - b).max())
        agree = float(np.mean(a.argmax(-1) == b.argmax(-1)))
        print(f"  Pallas vs XLA {name} logits: max abs diff {diff:.3e}, "
              f"max |logit| {scale:.3e}, relative {diff / scale:.3e} "
              f"(tolerance {LOGIT_RTOL:.0e}); argmax agreement {agree:.3f}")
        check(diff <= LOGIT_RTOL * scale,
              f"{name} logits: Pallas vs XLA relative diff "
              f"{diff / scale:.3e} > {LOGIT_RTOL:.0e}")
    for name, a, b in (("prefill", got[2], want[2]),
                       ("decode", got[3], want[3])):
        check(np.array_equal(a, b), f"{name}: located workers differ "
                                    f"(Pallas {a.any(0)}, XLA {b.any(0)})")
        check(a[:, 1].all() and a.sum() == a.shape[0],
              f"{name}: the locator did not single out corrupt worker 1 "
              f"in every group: {a.astype(int)}")
    print("  located per-group masks: worker 1 excluded in every group on "
          "both paths")


def describe(cfg, params) -> None:
    import jax
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"model {cfg.name}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.num_heads}/{cfg.num_kv_heads} heads x "
          f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
          f"{n / 1e9:.3f} B params ({cfg.param_dtype})")


def one_chip(seed: int, compiles: CompileCounter) -> None:
    import jax
    from repro import configs
    from repro.core.berrut import CodingConfig
    from repro.kernels import ops
    from repro.models import init_params
    from repro.serving import AdversaryConfig
    kind = ops.kernel_type()
    print(f"kernel type: {kind}")
    check(kind is ops.KernelType.PALLAS, f"TPU ops dispatch {kind}, "
                                         "not Pallas")
    cfg = configs.get_config(ARCH)
    params = init_params(cfg, jax.random.PRNGKey(seed))
    describe(cfg, params)
    lean = CodingConfig(k=4, s=1, e=0)
    print(f"pool K=4 S=1 E=0: {lean.num_workers} workers/group, "
          f"{POOL_GROUPS * lean.num_workers} coded streams")
    report("E=0", *serve(cfg, params, lean, compiles, seed=seed))
    robust = CodingConfig(k=4, s=1, e=1)
    print(f"pool K=4 S=1 E=1: {robust.num_workers} workers/group, "
          f"{POOL_GROUPS * robust.num_workers} coded streams, one "
          f"persistent attacker (sigma {BYZ_SIGMA:g})")
    attacker = AdversaryConfig(kind="persistent", num_adversaries=1,
                               sigma=BYZ_SIGMA, seed=seed)
    run = serve(cfg, params, robust, compiles, seed=seed,
                adversary=attacker)
    report("E=1", *run)
    m = run[0].metrics
    check(m.attacked_rounds > 0, "the attacker never reached a decode set")
    check(m.detection_tp > 0, "the locator never located the attacker, so "
                              "no per-group mask reached the decode kernel")
    compare_kernels(cfg, params, robust, seed)


def four_chips(seed: int, compiles: CompileCounter) -> None:
    import jax
    import numpy as np
    from repro import configs
    from repro.core.berrut import CodingConfig
    from repro.launch.mesh import make_worker_mesh
    from repro.launch.worker_mesh import WorkerShardConfig
    from repro.models import init_params, partitioning
    check(len(jax.devices()) >= 4,
          f"--four-chips needs 4 devices, found {len(jax.devices())}")
    cfg = configs.get_config(ARCH)
    params = init_params(cfg, jax.random.PRNGKey(seed))
    describe(cfg, params)
    coding = CodingConfig(k=4, s=4, e=0)
    print(f"pool K=4 S=4 E=0: {coding.num_workers} workers/group on a "
          f"4-way worker mesh vs W=1, {POOL_GROUPS * coding.num_workers} "
          f"coded streams")
    runs = {}
    for workers in (1, 4):
        with contextlib.ExitStack() as stack:
            if workers > 1:
                mesh = make_worker_mesh(workers)
                stack.enter_context(mesh)
                stack.enter_context(
                    partitioning.logical_sharding_context(mesh))
            run = serve(cfg, params, coding, compiles, seed=seed,
                        wshard=WorkerShardConfig())
            report(f"W={workers}", *run)
            runs[workers] = run[0]
    w1, w4 = runs[1], runs[4]
    check(w1.trace == w4.trace, "W=4 event trace differs from W=1")
    for uid in sorted(w1.results):
        check(np.array_equal(w1.results[uid], w4.results[uid]),
              f"request {uid}: W=4 tokens {w4.results[uid]} != W=1 "
              f"tokens {w1.results[uid]}")
    print(f"  W=4 sampled tokens equal W=1 for all {len(w1.results)} "
          f"requests")
    leaf = jax.tree.leaves(w4._state.caches)[0]
    shards = leaf.addressable_shards
    devices = {s.device for s in shards}
    streams = leaf.shape[1]
    print(f"  pool cache leaf {leaf.shape} ({leaf.sharding.spec}) in "
          f"{len(shards)} shards on {len(devices)} devices, stream rows "
          f"per shard {sorted({s.data.shape[1] for s in shards})}")
    check(len(devices) == 4, f"coded streams on {len(devices)} devices, "
                             "not 4")
    check(all(s.data.shape[1] == streams // 4 for s in shards),
          "coded streams are not split 4 ways over the worker axis")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-way worker-mesh pool and its W=1 "
                         "comparison (needs 4 devices)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # libtpu would otherwise log under /tmp, outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: jax.devices()[0].platform is "
             f"{devices[0].platform!r}; this smoke run needs a TPU and "
             "does not fall back")
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    print(f"device: {devices[0].device_kind} x {len(devices)}")
    if args.four_chips:
        four_chips(args.seed, compiles)
    else:
        one_chip(args.seed, compiles)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
