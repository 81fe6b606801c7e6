"""The traffic generator: seeded determinism, the same sizes in the same
order for every seed, lengths inside each traffic file's ranges; and the
window's p95 and rate arithmetic."""
import json
import pathlib

import numpy as np
import pytest

from bench.harness import ClosedLoop, Tracked, Window, reader, window_stats
from bench.traffic import Traffic, lengths, percentile, seed32

TRAFFIC = sorted((pathlib.Path(__file__).parent / "traffic").glob("*.json"))
BIG_SEED = 2**31 + 977


@pytest.mark.parametrize("path", TRAFFIC, ids=lambda p: p.stem)
def test_lengths_in_range_and_same_for_every_seed(path):
    spec = json.loads(path.read_text())
    a, b = Traffic(spec, BIG_SEED, 1000), Traffic(spec, 7, 1000)
    for key, lens in (("prompt", a.prompt_lens), ("output", a.output_lens)):
        assert lens.min() >= spec[key]["min"] and lens.max() <= spec[key]["max"]
    assert list(a.prompt_lens) == list(b.prompt_lens)
    assert list(a.output_lens) == list(b.output_lens)
    assert list(a.prompt_lens) != sorted(a.prompt_lens)  # clients get a mix
    for c in range(a.clients):
        ra, rb = a.next_for(c), b.next_for(c)
        assert len(ra.prompt) == len(rb.prompt) and ra.prompt != rb.prompt
        assert ra.max_new_tokens == rb.max_new_tokens


def test_same_seed_same_requests():
    spec = json.loads(TRAFFIC[0].read_text())
    a, b = Traffic(spec, BIG_SEED, 5000), Traffic(spec, BIG_SEED, 5000)
    for c in range(a.clients):
        for _ in range(3):
            ra, rb = a.next_for(c), b.next_for(c)
            assert ra == rb and all(0 <= t < 5000 for t in ra.prompt)
    assert Traffic(spec, 8, 5000).request(0) != a.request(0)


def test_lognormal_quantiles():
    lens = lengths({"dist": "lognormal", "median": 100, "sigma": 1.0, "min": 1, "max": 10**6}, 1001)
    assert np.median(lens) == 100
    u = lengths({"dist": "uniform", "min": 4, "max": 16}, 13)
    assert list(u) == list(range(4, 17))


def test_seed32_handles_large_seeds():
    assert 0 <= seed32(2**40 + 3) < 2**32
    assert seed32(5) == seed32(5) != seed32(6)


def test_percentile_and_window_arithmetic():
    assert percentile([], 95) is None
    assert percentile(list(range(101)), 95) == pytest.approx(95.0)
    loop = ClosedLoop(eng=None, traffic=None)

    class R:
        prompt, out_tokens, status = [1], [], None

    # stamps: one request submitted at 0, tokens at 1, 2, 4, 8; window (1.5, 8]
    loop.live = {0: Tracked(R(), 0, 0.0, [1.0, 2.0, 4.0, 8.0])}
    loop.done = [Tracked(R(), 1, 1.6, [3.0, 3.5])]
    w = window_stats(loop, 1.5, 8.0, 10**9, 42.0)
    assert w.tokens == 5  # 2, 4, 8 and 3, 3.5
    assert sorted(w.itl_s) == [0.5, 2.0, 4.0]  # (1, 2) straddles the start
    assert w.ttft_s == [pytest.approx(1.4)]  # only the request whose first token is inside
    assert reader("e2e", "tokens_per_s")(w) == pytest.approx(5 / 6.5)
    assert reader("e2e", "itl_p95_ms")(w) == pytest.approx(1e3 * percentile([0.5, 2, 4], 95))
    assert reader("e2e", "peak_hbm_gb")(w) == 1.0
    assert reader("e2e", "setup_s")(w) == 42.0
    assert reader("e2e", "ttft_p95_s")(Window(0, 1, 0, [], [], 0, 0)) is None
