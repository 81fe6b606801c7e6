"""Operations and bytes of the model step, from the configuration's shapes.

Counts are of the algorithm's useful work, whatever implements it: a
padding row, a masked key or a logit row nobody samples counts nothing.
"""
from __future__ import annotations


def packed_projections(dm) -> list[tuple[str, int, int]]:
    """(name, K, N) of each packed projection of one layer."""
    d, conv = dm.d_model, dm.d_inner + 2 * dm.d_state
    return [("in_z", d, dm.d_inner), ("in_xbc", d, conv), ("out_proj", dm.d_inner, d)]


def packed_params(dm) -> int:
    """Packed-projection parameters of the whole model."""
    return dm.n_layers * sum(k * n for _, k, n in packed_projections(dm))


def kernel_calls(dm, n_slots: int, chunk: int) -> list[tuple[int, int, int, int]]:
    """(M, K, N, calls) of the packed kernel per engine step: the Mamba2
    block runs lane by lane, one ``[S]`` row block per lane."""
    return [(n_slots, k, n, dm.n_layers * chunk) for _, k, n in packed_projections(dm)]


def matmul_least_s(m: int, k: int, n: int, w_bits: int, a_bits: int, peaks: dict) -> float:
    """Least time of one packed matmul call: the larger of its integer
    operations at the int8 peak and its logical bytes at HBM bandwidth.
    Logical bytes: packed weights, activation levels in, float32 out."""
    ops = 2.0 * m * k * n
    nbytes = k * n * w_bits / 8 + m * k * a_bits / 8 + m * n * 4
    return max(ops / peaks["int8_ops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


def step_kernel_least_s(dm, n_slots, chunk, w_bits, a_bits, peaks) -> float:
    return sum(c * matmul_least_s(m, k, n, w_bits, a_bits, peaks)
               for m, k, n, c in kernel_calls(dm, n_slots, chunk))


def useful_ops(dm, chunks: list[tuple[int, int]], n_sampled: int) -> tuple[float, float]:
    """(integer ops, float ops) of one step's useful work.

    ``chunks`` holds (start position, valid tokens) of each slot fed in
    the step; ``n_sampled`` counts the logit rows that were sampled.
    Integer ops: the packed projections of every valid token.  Float
    ops: the Mamba2 dt projection, conv and state update of every valid
    token, and the LM head of sampled rows.
    """
    rows = sum(n for _, n in chunks)
    per_row = sum(k * n for _, k, n in packed_projections(dm)) * dm.n_layers
    int_ops = 2.0 * rows * per_row
    conv = dm.d_inner + 2 * dm.d_state
    per_tok = (2 * dm.d_model * dm.ssm_heads + 2 * dm.conv_width * conv
               + 5 * dm.ssm_heads * dm.d_state * dm.head_dim)
    flt = float(rows * per_tok * dm.n_layers)
    flt += 2.0 * n_sampled * dm.d_model * dm.vocab
    return int_ops, flt


def least_s(int_ops: float, flt_ops: float, peaks: dict) -> float:
    """Least time of a step's useful work at the chip's peaks: integer
    work at the int8 peak, float work at the bf16 peak."""
    return int_ops / peaks["int8_ops_per_s"] + flt_ops / peaks["bf16_flops_per_s"]


# The packed kernel as the device trace shows it on a TPU: a Mosaic custom
# call (``tpu_custom_call``) whose result is the int32 accumulator (with the
# fused path, a tuple of it and the activation-level row sums).  Pallas
# kernels that move bfloat16 data, such as the paged KV gather, do not match.
PACKED_KERNEL = r'^%?[\w.-]+ = \(?s32\[.*? custom-call\(.*custom_call_target="tpu_custom_call"'
