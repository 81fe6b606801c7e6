"""The family modules: found by ``cfg.family`` and by path alone, refused
with the file to add where missing, named by no generic file, and the
Mamba2 weights and reference bit-identical to the digests taken before
they moved into ``families/ssm.py``."""
import hashlib
import pathlib
import re
import shutil

import jax
import numpy as np
import pytest

from bench import harness, model
from bench.reference import Reference
from bench.test_bench_rehearsal import SEED, _measure, fake_clock  # noqa: F401
from bench.traffic import seed32

BENCH = pathlib.Path(__file__).resolve().parent

# sha256 of the smoke preset's packed weight tree (every leaf's path,
# dtype, shape and bytes), and of the reference's logits rows on a fixed
# sample, float32 and its fp8 control, computed before the move
TREE = {
    1: "722eb95731530a851104a5fffe719d11f520b0408fb1a7cd74e5fe7bad5f38d0",
    SEED: "9ffee620ebf34e923246234c68c671920c325266a778d5e091c0768bef4f50f4",
}
ROWS = {
    (1, None): "3810426bbfd182280d1f2cf3634a51286e8abfdc2a132a9e815d005844644eec",
    (1, "fp8"): "2a7264ea493c3acc287fead74287a209ee5dbed4fd1e5332dd01c5e113fd89d7",
    (SEED, None): "a588c02abd6fd905a7ab4152cced5ee044a2b131739bd9465cace554b54b5b58",
    (SEED, "fp8"): "63a8c0046b24fc136046e2920c45ab75003b3b2322006cfa1b9918708aad7319",
}


@pytest.fixture(scope="module")
def mamba():
    return model.load_model("mamba2-130m", smoke=True)


@pytest.mark.parametrize("seed", sorted(TREE))
def test_packed_tree_bit_identical(mamba, seed):
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(model.make_packed_params(mamba, seed)):
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}".encode())
        h.update(a.tobytes())
    assert h.hexdigest() == TREE[seed]


@pytest.mark.parametrize("seed,lowp", sorted(ROWS, key=str))
def test_reference_rows_bit_identical(mamba, seed, lowp):
    rng = np.random.default_rng(7)
    seqs = [[int(t) for t in rng.integers(0, mamba.cfg.vocab, n)] for n in (40, 17, 63)]
    targets = [(r, p, 0) for r, s in enumerate(seqs) for p in range(0, len(s), 5)]
    rows = Reference(mamba, lowp=lowp).logits_at(seed32(seed), seqs, targets, (4, 128))
    assert rows.shape == (25, mamba.cfg.vocab)
    assert hashlib.sha256(np.ascontiguousarray(rows, np.float32).tobytes()).hexdigest() \
        == ROWS[(seed, lowp)]


def test_full_preset_passes_its_family_check():
    m = model.load_model("mamba2-130m")
    assert m.family is model.family("ssm")
    assert m.dims == m.family.Dims(24, 768, 50432, d_inner=1536, d_state=128,
                                   ssm_heads=24, head_dim=64)


def test_family_without_module_is_refused(tmp_path, monkeypatch):
    monkeypatch.setattr(model, "FAMILIES", tmp_path / "bench" / "families")
    with pytest.raises(ValueError, match=re.escape("add ") + ".*bench/families/ssm\\.py"):
        model.load_model("mamba2-130m", smoke=True)


def test_family_module_found_by_path_passes_smoke_rehearsal(tmp_path, monkeypatch, fake_clock):
    fams = tmp_path / "families"
    fams.mkdir()
    shutil.copy(BENCH / "families" / "ssm.py", fams / "ssm.py")
    monkeypatch.setattr(model, "FAMILIES", fams)
    cell = harness.load_cell("mamba2.chat", smoke=True)
    assert pathlib.Path(cell.model.family.__file__) == fams / "ssm.py"
    out = _measure(cell)
    assert out["correct"] is True, out["checks"]


def _family_values() -> set[str]:
    from repro.configs import ARCHS, get_config
    return {get_config(a, smoke=True).family for a in ARCHS}


def test_generic_files_name_no_family():
    words = "|".join(sorted(_family_values() | {"mamba"}))
    pattern = re.compile(rf"(?i)mamba|ssm|\b({words})\b")
    generic = [p for p in BENCH.rglob("*.py")
               if "families" not in p.relative_to(BENCH).parts
               and not p.name.startswith("test_")]
    assert BENCH / "model.py" in generic and BENCH / "metrics" / "step_mfu.py" in generic
    named = [f"{p.relative_to(BENCH)}:{i}: {line.strip()}" for p in generic
             for i, line in enumerate(p.read_text().splitlines(), 1) if pattern.search(line)]
    assert named == []
