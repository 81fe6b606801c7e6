"""Plain float32 reference of the served model, computed in blocks.

Imports nothing of the program.  It rebuilds each layer's float weights
from the seed (the family's ``layer_float``), applies its own copy of
the DoReFa fake quantization to the projections (weights: tanh-normalised
``w_bits`` levels in [-1, 1]; activations: ``a_bits`` levels of
``sigmoid(x)``), and runs whole sequences -- prompt and served tokens --
one layer at a time at ``highest`` matmul precision: the embedding here,
then the family's ``ref_layer`` for each layer and its ``ref_head`` at
the chosen positions.

``lowp`` names the control: the same reference with every quantity that
the configuration keeps in bfloat16 -- the embedding, each projection's
input to the activation quantizer (``sigmoid(x)``) and its output, the
residual stream, norms' outputs and what the family's layer rounds with
it -- rounded to a lower precision (``"fp8"``: float8 e4m3 after scaling
each row's absmax; ``"int8"``: one absmax scale per row).  The float32
reference weights are not rounded.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
EPS = 1e-6


def fq_weight(w, bits):
    t = jnp.tanh(w)
    t = t / (2.0 * jnp.max(jnp.abs(t)) + 1e-12) + 0.5
    n = (1 << bits) - 1
    return 2.0 * (jnp.round(t * n) / n) - 1.0


def fq_act(s, bits):
    """Levels of an activation already squashed into [0, 1]."""
    n = (1 << bits) - 1
    return jnp.round(jnp.clip(s, 0.0, 1.0) * n) / n


def int8_rows(x):
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0 + 1e-30
    return jnp.round(x / s) * s


def fp8_rows(x):
    """Round to float8 e4m3 (3 mantissa bits, exponents down to -6) after
    scaling each row's absmax to 448, in float32 arithmetic."""
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 448.0 + 1e-30
    y = x / s
    e = jnp.maximum(jnp.floor(jnp.log2(jnp.maximum(jnp.abs(y), 1e-30))), -6.0)
    q = jnp.exp2(e - 3.0)
    return jnp.round(y / q) * q * s


LOWP = {None: lambda a: a, "int8": int8_rows, "fp8": fp8_rows}


def rmsnorm(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * g


def qdense(x, w, bits, rnd):
    wb, ab = bits
    return jnp.matmul(fq_act(rnd(jax.nn.sigmoid(x)), ab), fq_weight(w, wb), precision=HI)


class Reference:
    """Jitted blocks of the reference for one model: embedding, one layer
    (weights rebuilt from the seed inside), and the final norm and LM
    head at chosen positions.  ``lowp`` selects the control's rounding
    (see the module)."""

    def __init__(self, model, *, lowp: str | None = None):
        fam, dm, bits = model.family, model.dims, (model.w_bits, model.a_bits)
        rnd = LOWP[lowp]
        self.n_layers = dm.n_layers
        self.embed = jax.jit(lambda s32, tokens: rnd(fam.top_float(s32, dm)["embed"][tokens]))
        self.layer = jax.jit(
            lambda s32, i, x: fam.ref_layer(fam.layer_float(s32, dm, i), x, dm, bits, rnd))
        self.head = jax.jit(
            lambda s32, x, rows, cols: fam.ref_head(fam.top_float(s32, dm), x[rows, cols], dm, rnd))

    def logits_at(self, seed32: int, seqs: list[list[int]], targets: list[tuple[int, int, int]],
                  shape: tuple[int, int] | None = None, rows: int = 256) -> np.ndarray:
        """Logits [len(targets), V] at ``targets`` = (sequence, position,
        token) triples; the token is not read.  Sequences are right-padded
        into one ``shape = (batch, length)`` block (default: just large
        enough) -- the family's layer is causal, so padding changes no
        target, and a fixed shape compiles once -- and the head runs
        ``rows`` targets at a time."""
        B, T = shape or (len(seqs), max(len(s) for s in seqs))
        if len(seqs) > B or max(len(s) for s in seqs) > T:
            raise ValueError(f"sequences do not fit the reference block {B}x{T}")
        tokens = np.zeros((B, T), np.int32)
        for r, s in enumerate(seqs):
            tokens[r, : len(s)] = s
        s32 = jnp.uint32(seed32)
        x = self.embed(s32, jnp.asarray(tokens))
        for i in range(self.n_layers):
            x = self.layer(s32, jnp.int32(i), x)
        pos = np.zeros((-(-len(targets) // rows) * rows, 2), np.int32)
        pos[: len(targets)] = [t[:2] for t in targets]
        out = [np.asarray(self.head(s32, x, jnp.asarray(pos[i : i + rows, 0]),
                                    jnp.asarray(pos[i : i + rows, 1])))
               for i in range(0, len(pos), rows)]
        return np.concatenate(out)[: len(targets)]


def served_targets(requests) -> tuple[list[list[int]], list[tuple[int, int, int]]]:
    """For each (prompt, served tokens): the sequence the reference reads
    (prompt and every served token but the last) and, for each served
    token, (sequence index, position whose logits chose it, token)."""
    seqs, targets = [], []
    for r, (prompt, served) in enumerate(requests):
        seqs.append(list(prompt) + list(served[:-1]))
        for j, tok in enumerate(served):
            targets.append((r, len(prompt) - 1 + j, int(tok)))
    return seqs, targets


def gaps(logits, tokens) -> np.ndarray:
    """How far each token's logit lies below the row's best."""
    lg = np.asarray(logits, np.float64)
    tok = np.asarray(tokens, np.int64)
    return lg.max(axis=1) - lg[np.arange(len(tok)), tok]


def nmse(rows, ref) -> np.ndarray:
    """Per row: the energy of ``rows - ref`` about its mean, as a share of
    the energy of ``ref`` about its mean.  Each 4-bit activation level
    that a rounding moves adds its own small term to a logits row, so
    this share counts moved levels where the error's norm would grow as
    their square root."""
    r = np.asarray(ref, np.float64)
    d = np.asarray(rows, np.float64) - r
    d -= d.mean(axis=1, keepdims=True)
    r = r - r.mean(axis=1, keepdims=True)
    return (d * d).sum(axis=1) / (r * r).sum(axis=1)
