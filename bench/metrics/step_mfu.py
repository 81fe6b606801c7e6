"""Model step: the least time the chip's peaks allow for the useful work
of the traced steps (packed projections at the int8 peak, the SSM's dt
projection, conv and state update and the LM head at the bf16 peak; padding rows count nothing), over the
traced stretch's seconds, in percent."""
from bench import counts


def read(ctx):
    if not ctx.steps or ctx.trace.window_s <= 0:
        return None
    least = sum(counts.least_s(*counts.useful_ops(ctx.dims, r.chunks, r.n_sampled), ctx.peaks)
                for r in ctx.steps)
    return 100.0 * least / ctx.trace.window_s
