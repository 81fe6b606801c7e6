"""The one traffic generator: a closed loop of clients over a request pool.

A traffic file (``traffic/<name>.json``) gives the number of clients, the
pool size and the prompt and output length distributions.  The lengths,
their pairing and their order are the traffic file's alone -- each length
is a quantile of its distribution at evenly spaced probabilities, shuffled
once by a fixed generator -- and ``--seed`` draws only the token ids.  A
closed loop's window covers part of the pool, so a seed that reordered
the sizes would change the work in the window; with one order, every seed
offers the same work and the spread between runs is the system's.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles ``(i + 0.5) / n`` of ``spec``,
    clipped to ``[min, max]``; sorted, independent of any seed."""
    p = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(q)) for q in p])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        vals = spec["min"] + p * (spec["max"] + 1 - spec["min"])
        vals = np.floor(vals)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


@dataclasses.dataclass(frozen=True)
class RequestSpec:
    index: int
    prompt: list[int]
    max_new_tokens: int


class Traffic:
    """Request pool; client ``c`` sends requests ``c, c + clients,
    c + 2 * clients, ...`` one after the other (closed loop).  Sizes come
    from the traffic file, token ids from the seed."""

    def __init__(self, spec: dict, seed: int, vocab: int):
        self.spec = spec
        self.seed = seed
        self.vocab = vocab
        self.clients = int(spec["clients"])
        n = int(spec["pool"])
        order = np.random.default_rng(0)
        self.prompt_lens = order.permutation(lengths(spec["prompt"], n))
        self.output_lens = order.permutation(lengths(spec["output"], n))
        self._next = list(range(self.clients))

    def request(self, index: int) -> RequestSpec:
        n = len(self.prompt_lens)
        i = index % n
        rng = np.random.default_rng([self.seed, 1, index])
        prompt = rng.integers(0, self.vocab, int(self.prompt_lens[i])).tolist()
        return RequestSpec(index, prompt, int(self.output_lens[i]))

    def next_for(self, client: int) -> RequestSpec:
        index = self._next[client]
        self._next[client] += self.clients
        return self.request(index)


def seed32(seed: int) -> int:
    """A 32-bit key for JAX's PRNG from a seed of any size."""
    return int(np.random.SeedSequence(seed).generate_state(1, np.uint32)[0])


def percentile(values, q: float) -> float | None:
    """Linear-interpolated percentile (numpy's default); None when empty."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))
