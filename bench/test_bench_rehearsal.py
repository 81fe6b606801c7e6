"""CPU rehearsal: one tiny cell driven through the harness's inner
functions on the smoke presets, in Pallas interpret mode.  Checks the
result line's keys and that ``correct`` is computed -- false when the
timed path is broken underneath -- and never reads timings."""
import json
import os
import pathlib
import shutil
import subprocess
import sys
import jax
import pytest
from bench import harness

REPO = pathlib.Path(__file__).resolve().parents[1]
PEAKS = {"bf16_flops_per_s": 1e12, "int8_ops_per_s": 2e12, "hbm_bytes_per_s": 1e11}
SEED = 2**31 + 5
# The smoke preset (2 layers of width 64) over a 0.6 s window of the fake
# clock, checked against the committed limits: on seeds 1, 2, 3, 9 and
# SEED a sound run reads logit_nmse_max 0.0028-0.0043 and the fp8 control
# 0.0103-0.0149 (this module's CPU run).
SMOKE_SEEDS = (1, SEED)
WINDOW = 0.6


class FakeTime:
    """A clock that advances 10 ms per reading, so a window holds the same
    steps on any machine and the smoke readings repeat exactly."""

    def __init__(self):
        self.t = 0.0

    def monotonic(self) -> float:
        self.t += 0.01
        return self.t


@pytest.fixture
def fake_clock(monkeypatch):
    monkeypatch.setattr(harness, "time", FakeTime())


def _smoke(name: str) -> harness.Cell:
    return harness.load_cell(name, smoke=True)


def _measure(cell, seed: int = SEED):
    return harness.measure(cell, seed, WINDOW, False, PEAKS, 0.0, jax.devices())


def test_result_line_keys_and_correct(fake_clock):
    cell = _smoke("mamba2.chat")
    out = _measure(cell)
    assert list(out) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 16
    want = {m["name"] for m in cell.end_to_end}
    if not out["device"]["memory_peak_bytes"]:  # the CPU keeps no peak
        want.discard("peak_hbm_gb")
    assert set(out["metrics"]) == want
    units = {m["name"]: m["unit"] for m in cell.end_to_end}
    assert all(v["unit"] == units[k] for k, v in out["metrics"].items())
    assert set(out["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert list(out["checks"]) == list(cell.limits)
    assert all(0.0 <= c["value"] <= c["limit"] for c in out["checks"].values())
    json.dumps(out)


@pytest.mark.parametrize("seed", SMOKE_SEEDS)
def test_sound_smoke_reads_under_committed_limits(fake_clock, seed):
    out = _measure(_smoke("mamba2.chat"), seed)
    assert out["correct"] is True, out["checks"]


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_run_refuses_without_tpu():
    p = _run(REPO, "--workload", "mamba2.chat", "--seed", "1", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_run_fails_without_the_program(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, "--workload", "mamba2.chat", "--seed", "1", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""
