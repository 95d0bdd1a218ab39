"""Kernel ``kernels/berrut_decode.fused_group_decode`` (the fused
Berrut decode tail): the least time the chip needs to read the live
groups' (N+1, V) coded logits and write their (K, V) decoded logits
(``work.tail``), over the kernel's device time in the traced calls.
Moves ``tokens_per_s``."""

import trace_reduce
import work


def is_kernel(op) -> bool:
    """The kernel's ops: HLO text ``%fused_group_decode.N = ... custom-call``."""
    return (op.name.startswith("%fused_group_decode")
            and "tpu_custom_call" in op.name)


def read(ctx):
    if ctx.trace is None:
        return None
    seconds = trace_reduce.op_seconds(ctx.trace, is_kernel)
    if seconds <= 0.0:
        return None
    on, off = ctx.tracer.t_on, ctx.tracer.t_off
    least = 0.0
    for c in ctx.calls:
        if c.t0 < on or c.t1 > off:
            continue
        flops, bytes_ = work.tail(ctx.coding.k, ctx.coding.workers,
                                  ctx.dims.vocab, int(c.group_mask.sum()))
        t, _ = work.roofline_s(flops, bytes_, ctx.peaks["flops_bf16"],
                               ctx.peaks["hbm_bytes_per_s"])
        least += t
    return 100.0 * least / seconds
