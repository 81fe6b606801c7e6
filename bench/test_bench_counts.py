"""Operation and byte counters against hand counts, and the two roofline
shares at exactly 100% on a perfect synthetic trace."""
import dataclasses

import pytest

from bench import counts, xplane
from bench.harness import LayerContext, StepRecord, reader
from bench.model import Dims

MAMBA = Dims(24, 768, 50432, d_inner=1536, d_state=128, ssm_heads=24, head_dim=64)
PEAKS = {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}
# event names as a TPU v5e trace of the mamba2-130m step shows them
KERNEL_TEXT = ('%run.18 = s32[2,16,896]{2,1,0:T(8,128)S(1)} custom-call(s32[16,768]{1,0:T(8,128)S(1)} '
               '%convert_bitcast_fusion.16, s32[768,896]{1,0:T(8,128)S(1)} %get-tuple-element.874), '
               'custom_call_target="tpu_custom_call", operand_layout_constraints={s32[16,768]{1,0}, '
               's32[768,896]{1,0}}, frontend_attributes={kernel_metadata={}}')
WHILE_TEXT = ('%while.31 = (s32[]{:T(128)}, bf16[16,16,768]{2,0,1:T(8,128)(2,1)S(1)}, '
              '/*index=5*/bf16[24,1792]{1,0:T(8,128)(2,1)S(1)}) while((s32[]{:T(128)}) %tuple.3), '
              'condition=%cond, body=%body')
FUSION_TEXT = ('%multiply_reduce_fusion.2 = (bf16[16,24,64]{2,1,0:T(8,128)(2,1)S(1)}, '
               'f32[16,24,128,64]{3,2,1,0:T(8,128)S(1)}) fusion(f32[16,24,128,64]{3,2,1,0:T(8,128)S(1)} '
               '%get-tuple-element.846, %custom-call.3), kind=kLoop, calls=%fused_computation.3')
GATHER_TEXT = ('%run.9 = bf16[8,16,128]{2,1,0} custom-call(bf16[100,16,128]{2,1,0} %kv, s32[8,64]{1,0} '
               '%table), custom_call_target="tpu_custom_call"')


def test_trace_names_and_packed_kernel_pattern():
    import re
    assert xplane.hlo_name_op(KERNEL_TEXT) == ("run.18", "custom-call")
    assert xplane.hlo_name_op(WHILE_TEXT) == ("while.31", "while")
    assert xplane.hlo_name_op(FUSION_TEXT) == ("multiply_reduce_fusion.2", "fusion")
    assert xplane.hlo_name_op("bench.step") == ("bench.step", "")
    hits = [t for t in (KERNEL_TEXT, WHILE_TEXT, FUSION_TEXT, GATHER_TEXT)
            if re.search(counts.PACKED_KERNEL, t)]
    assert hits == [KERNEL_TEXT]


def test_packed_params_hand_counts():
    # Mamba2-130m: in_z 768x1536, in_xbc 768x(1536 + 2*128), out_proj 1536x768
    per = 768 * 1536 + 768 * 1792 + 1536 * 768
    assert counts.packed_params(MAMBA) == 24 * per == 89_653_248


def test_kernel_calls_per_step():
    mb = counts.kernel_calls(MAMBA, 16, 16)
    assert {(m, c) for m, _, _, c in mb} == {(16, 24 * 16)} and len(mb) == 3


def test_matmul_least_time_hand_count():
    # M=128, K=N=4096 at w4a4: 2*M*K*N ops against weights + acts in + out
    ops = 2 * 128 * 4096 * 4096
    nbytes = 4096 * 4096 / 2 + 128 * 4096 / 2 + 128 * 4096 * 4
    want = max(ops / 393e12, nbytes / 819e9)
    assert counts.matmul_least_s(128, 4096, 4096, 4, 4, PEAKS) == pytest.approx(want)
    assert want == pytest.approx(nbytes / 819e9)  # bandwidth-bound even at 128 rows
    # one Mamba2 lane (M=16) is bandwidth-bound
    m = counts.matmul_least_s(16, 768, 1536, 4, 4, PEAKS)
    assert m == pytest.approx((768 * 1536 / 2 + 16 * 768 / 2 + 16 * 1536 * 4) / 819e9)


def test_useful_ops_counts_valid_rows_only():
    # one slot decoding at position 99 (1 token) and one prefilling 16 at 0
    i_ops, f_ops = counts.useful_ops(MAMBA, [(99, 1), (0, 16)], n_sampled=1)
    assert i_ops == 2.0 * 17 * counts.packed_params(MAMBA)
    # per token and layer: dt projection 768x24, conv 4 taps over 1792
    # channels, state update and read-out over 24 heads x 128 x 64
    per_tok = 2 * 768 * 24 + 2 * 4 * 1792 + 5 * 24 * 128 * 64
    assert f_ops == pytest.approx(17 * 24 * per_tok + 2.0 * 768 * 50432)
    i0, f0 = counts.useful_ops(MAMBA, [], n_sampled=0)
    assert (i0, f0) == (0.0, 0.0)


@dataclasses.dataclass
class _Model:
    dims: Dims
    engine: dict
    w_bits: int = 4
    a_bits: int = 4


@dataclasses.dataclass
class _Cell:
    model: _Model


@pytest.mark.parametrize("dm,shape", [(MAMBA, (16, 16)), (MAMBA, (8, 4))])
def test_roofline_and_mfu_at_most_100_on_perfect_trace(dm, shape):
    s, c = shape
    cell = _Cell(_Model(dm, {"n_slots": s, "chunk_tokens": c}))
    steps = [StepRecord(0.0, 1.0, [(0, c)] * s, s, traced=True)] * 3
    kernel = 3 * counts.step_kernel_least_s(dm, s, c, 4, 4, PEAKS)
    useful = 3 * counts.least_s(*counts.useful_ops(dm, [(0, c)] * s, s), PEAKS)
    # kernels that run at their roofline, back to back; and a stretch that
    # lasts exactly the least time of the step's useful work
    ev = [xplane.Event("run.1", 0.0, kernel, KERNEL_TEXT)]

    def ctx(seconds):
        host = [xplane.Event(xplane.WINDOW_SPAN, 0.0, seconds)]
        return LayerContext(cell, xplane.reduce({"/device:TPU:0": ev}, host), steps, PEAKS, steps)

    assert reader("metrics", "packed_matmul_roofline")(ctx(kernel)) == pytest.approx(100.0)
    assert reader("metrics", "step_mfu")(ctx(useful)) == pytest.approx(100.0)
    assert reader("metrics", "row_util")(ctx(useful)) == pytest.approx(100.0)
