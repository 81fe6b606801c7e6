"""The check fails when the timed path is broken underneath: the harness
is driven past its look for a chip, on the smoke preset on the CPU and
against the committed limits, with the engine's fused step altering each
sampled token, or returning the state it was given (no SSM or conv state
written)."""
import jax
import jax.numpy as jnp
import pytest

from bench.test_bench_rehearsal import SMOKE_SEEDS, _measure, _smoke, fake_clock  # noqa: F401


def _break(monkeypatch, fault: str):
    """Patch ``build_engine`` so the engine's fused step is broken."""
    import repro.serving as serving
    from repro.models import transformer as T

    real = serving.build_engine

    def build(cfg, ecfg, **kw):
        eng = real(cfg, ecfg, **kw)
        step = eng._step
        if fault == "token_altered":
            def bad(*args):
                logits, state = step(*args)
                return jnp.roll(logits, 1, axis=-1), state
        else:  # state_unchanged: the step returns the state it was given
            keep = jax.jit(lambda p, st, table, tok, pos, lens: T.forward_decode_paged(
                p, cfg, st, table, tok, pos, lens=lens))

            def bad(p, state, *rest):
                return keep(p, state, *rest)[0], state
        eng._step = bad
        return eng

    monkeypatch.setattr(serving, "build_engine", build)


@pytest.mark.parametrize("seed", SMOKE_SEEDS)
@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
def test_broken_step_is_not_correct(monkeypatch, fake_clock, fault, seed):
    _break(monkeypatch, fault)
    out = _measure(_smoke("mamba2.chat"), seed)
    assert out["correct"] is False, out["checks"]
