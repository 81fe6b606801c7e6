"""Packed matmul kernel: device time in the kernel's events over device
busy time, in percent."""
from bench import counts, xplane


def read(ctx):
    secs, n = xplane.op_seconds(ctx.trace, counts.PACKED_KERNEL)
    if n == 0 or ctx.trace.busy_s <= 0:
        return None
    return 100.0 * secs / ctx.trace.busy_s
