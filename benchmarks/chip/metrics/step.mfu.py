"""Whole step: useful model operations per second over the chips'
peak.  Useful are only real queries' prompt tokens at admission and
their generated tokens (``work.py``); coded redundancy, padding rows and
recomputed slots are not.  Moves ``tokens_per_s``."""

import work


def read(ctx):
    flops = 0.0
    for s in ctx.served.values():
        if not s.tokens:
            continue
        flops += work.prompt_flops(ctx.arch, ctx.dims, ctx.prompt_len)
        for j in range(1, len(s.tokens)):
            flops += work.token_flops(ctx.arch, ctx.dims,
                                     ctx.prompt_len + j - 1)
    peak = ctx.peaks["flops_bf16"] * ctx.chips
    return 100.0 * flops / (ctx.window_s * peak)
