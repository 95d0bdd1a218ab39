"""Kernel ``kernels/flash_decode.pool_flash_decode`` (pool decode
attention): the least time the chip needs for the live slots' attention
(``work.pool_attention``: each live stream over its cache up to its
position), over the kernel's device time in the traced decode calls.
Moves ``tokens_per_s``."""

import collections

import trace_reduce
import work


def is_kernel(op) -> bool:
    """The kernel's ops: HLO text ``%pool_flash_decode.N = ... custom-call``."""
    return (op.name.startswith("%pool_flash_decode")
            and "tpu_custom_call" in op.name)


def read(ctx):
    if ctx.trace is None:
        return None
    seconds = trace_reduce.op_seconds(ctx.trace, is_kernel)
    if seconds <= 0.0:
        return None
    keys = collections.defaultdict(list)
    for run in ctx.runs.values():
        for j, c in enumerate(run.calls[1:], start=1):
            keys[c].append(ctx.prompt_len + j)
    on, off = ctx.tracer.t_on, ctx.tracer.t_off
    least = 0.0
    for i, c in enumerate(ctx.calls):
        if c.kind != "decode" or c.t0 < on or c.t1 > off:
            continue
        flops, bytes_ = work.pool_attention(
            ctx.dims, [(ctx.coding.workers, n) for n in keys[i]])
        t, _ = work.roofline_s(flops, bytes_, ctx.peaks["flops_bf16"],
                               ctx.peaks["hbm_bytes_per_s"])
        least += ctx.dims.layers * t
    return 100.0 * least / seconds
