"""The traffic generator: the same seed gives the same queue, every seed
the same work in the same order, and prompt lengths stay on the mix's
grid."""
import json

import numpy as np
import pytest
from chipbench_tiny import BENCH

from chipbench import traffic

SERVE_MIXES = ["docs-16x4096"]


def _mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", SERVE_MIXES)
def test_same_seed_same_queue(name):
    mix = _mix(name)
    a = traffic.requests(mix, np.random.default_rng([2 ** 33 + 5, 2]), 50, 1000)
    b = traffic.requests(mix, np.random.default_rng([2 ** 33 + 5, 2]), 50, 1000)
    c = traffic.requests(mix, np.random.default_rng([6, 2]), 50, 1000)
    assert all(np.array_equal(p, q) and o == r for (p, o), (q, r) in zip(a, b))
    assert any(not np.array_equal(p, q) for (p, _), (q, _) in zip(a, c))


@pytest.mark.parametrize("name", SERVE_MIXES)
def test_every_seed_gets_the_same_work(name):
    mix = _mix(name)
    n = 3 * mix["deck"]
    sizes = [[(len(p), o) for p, o in traffic.requests(
        mix, np.random.default_rng(seed), n, 1000)] for seed in (1, 2, 3)]
    assert sizes[0] == sizes[1] == sizes[2]


@pytest.mark.parametrize("name", SERVE_MIXES)
def test_lengths_on_grid_and_in_range(name):
    mix = _mix(name)
    p, o = mix["prompt"], mix["output"]
    for prompt, out in traffic.requests(mix, np.random.default_rng(9),
                                        4 * mix["deck"], 1000):
        assert len(prompt) % p["grid"] == 0
        assert p["min"] <= len(prompt) <= p["max"]
        assert o["min"] <= out <= o["max"]
        assert len(prompt) + out <= mix["max_seq_len"] + 1
        assert prompt.dtype == np.int32 and 0 <= prompt.min() <= prompt.max() < 1000
    lengths = traffic.grid_lengths(mix)
    assert lengths[0] >= p["min"] and lengths[-1] == p["max"]
    warm = traffic.warmup_requests(mix, np.random.default_rng(1), 1000)
    assert sorted({len(q) for q, _ in warm}) == lengths
    assert len(warm) >= mix["slots"]


def test_docs_mix_has_fourteen_lengths():
    assert traffic.grid_lengths(_mix("docs-16x4096")) == list(
        range(512, 3841, 256))
