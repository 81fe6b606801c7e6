"""Engine scheduler: token rows fed over rows computed in the window's
steps (``n_slots * chunk_tokens`` per step), in percent."""


def read(ctx):
    if not ctx.window:
        return None
    eng = ctx.cell.model.engine
    fed = sum(n for r in ctx.window for _, n in r.chunks)
    return 100.0 * fed / (len(ctx.window) * eng["n_slots"] * eng["chunk_tokens"])
