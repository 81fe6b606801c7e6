"""Operations and bytes of the model step, from the configuration's shapes.

Counts are of the algorithm's useful work, whatever implements it: a
padding row, a masked key or a logit row nobody samples counts nothing.
What a step issues and needs is the family's (``kernel_calls`` and
``useful_ops`` of ``families/<family>.py``); what they cost at the
chip's peaks is here.
"""
from __future__ import annotations


def matmul_least_s(m: int, k: int, n: int, w_bits: int, a_bits: int, peaks: dict) -> float:
    """Least time of one packed matmul call: the larger of its integer
    operations at the int8 peak and its logical bytes at HBM bandwidth.
    Logical bytes: packed weights, activation levels in, float32 out."""
    ops = 2.0 * m * k * n
    nbytes = k * n * w_bits / 8 + m * k * a_bits / 8 + m * n * 4
    return max(ops / peaks["int8_ops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


def kernel_least_s(calls, w_bits: int, a_bits: int, peaks: dict) -> float:
    """Least time of the packed kernel's ``(M, K, N, calls)`` calls."""
    return sum(c * matmul_least_s(m, k, n, w_bits, a_bits, peaks) for m, k, n, c in calls)


def least_s(int_ops: float, flt_ops: float, peaks: dict) -> float:
    """Least time of a step's useful work at the chip's peaks: integer
    work at the int8 peak, float work at the bf16 peak."""
    return int_ops / peaks["int8_ops_per_s"] + flt_ops / peaks["bf16_flops_per_s"]


# The packed kernel as the device trace shows it on a TPU: a Mosaic custom
# call (``tpu_custom_call``) whose result is the int32 accumulator (with the
# fused path, a tuple of it and the activation-level row sums).  Pallas
# kernels that move bfloat16 data, such as the paged KV gather, do not match.
PACKED_KERNEL = r'^%?[\w.-]+ = \(?s32\[.*? custom-call\(.*custom_call_target="tpu_custom_call"'
