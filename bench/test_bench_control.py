"""The control comes out not correct: the float32 reference with every
quantity the configuration keeps in bfloat16 rounded to fp8, put in the
program's place, on the smoke preset on the CPU, against the committed
limits."""
import jax
import pytest

from bench import harness
from bench.test_bench_rehearsal import SMOKE_SEEDS, WINDOW, _smoke, fake_clock  # noqa: F401


@pytest.mark.parametrize("seed", SMOKE_SEEDS)
def test_control_fails_the_check(fake_clock, seed):
    cell = _smoke("mamba2.chat")
    s = harness.serve(cell, seed, WINDOW, False, 0.0, jax.devices())
    ref, _ = harness.reference_rows(cell, seed, s.sample)
    rows, _ = harness.reference_rows(cell, seed, s.sample, lowp="fp8")
    control = harness.compare(ref, rows, rows.argmax(axis=1))
    program = harness.program_readings(cell, seed, s.sample)
    limit = cell.limits["logit_nmse_max"]["limit"]
    assert program["logit_nmse_max"] <= limit < control["logit_nmse_max"], (program, control)
