"""Packed matmul kernel: the least time of the traced steps' kernel calls
(the family's ``kernel_calls``; per call the larger of 2*M*K*N at the
int8 peak and its logical bytes at HBM bandwidth) over the summed device
time of the kernel's events, in percent.  Nothing is returned when the
trace shows no kernel event."""
from bench import counts, xplane


def read(ctx):
    secs, n = xplane.op_seconds(ctx.trace, counts.PACKED_KERNEL)
    if n == 0 or secs <= 0:
        return None
    m = ctx.cell.model
    calls = m.family.kernel_calls(ctx.dims, m.engine["n_slots"], m.engine["chunk_tokens"])
    per_step = counts.kernel_least_s(calls, m.w_bits, m.a_bits, ctx.peaks)
    return 100.0 * per_step * len(ctx.steps) / secs
