"""Model step: the least time the chip's peaks allow for the useful work
of the traced steps (the family's ``useful_ops``: integer work at the
int8 peak, float work at the bf16 peak; padding rows count nothing),
over the traced stretch's seconds, in percent."""
from bench import counts


def read(ctx):
    if not ctx.steps or ctx.trace.window_s <= 0:
        return None
    fam = ctx.cell.model.family
    least = sum(counts.least_s(*fam.useful_ops(ctx.dims, r.chunks, r.n_sampled), ctx.peaks)
                for r in ctx.steps)
    return 100.0 * least / ctx.trace.window_s
