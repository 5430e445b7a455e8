"""Traffic from a mix file and a seed: deterministic, seed-independent in
its sizes, and found by name."""
import json
from itertools import islice

import numpy as np
import pytest

from bench import traffic

DATA = traffic.MIX_DIR.parent / "tests" / "data" / "mixes"


def _take(mix, seed, n, vocab=1000):
    return list(islice(traffic.requests(mix, seed, vocab), n))


MIXES = sorted(p.stem for p in traffic.MIX_DIR.glob("*.json"))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = traffic.load_mix(name)
    seed = 2 ** 33 + 17                      # wider than 32 bits
    a, b = _take(mix, seed, 80), _take(mix, seed, 80)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert x.max_new_tokens == y.max_new_tokens and x.t == y.t


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_sizes_in_another_order(name):
    mix = traffic.load_mix(name)
    g = traffic.GRID
    a, b = _take(mix, 1, g), _take(mix, 2, g)
    assert sorted(len(r.prompt) for r in a) == \
        sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new_tokens for r in a) == \
        sorted(r.max_new_tokens for r in b)
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in b]
    ragged = len({len(r.prompt) for r in a}) > 1
    if ragged:
        assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


@pytest.mark.parametrize("spec", [
    {"median": 20, "sigma": 0.5, "min": 5, "max": 40},
    {"min": 4, "max": 12}, {"min": 128, "max": 128}])
def test_lengths_respect_bounds(spec):
    p = traffic.length_grid(spec)
    assert p.min() >= spec["min"] and p.max() <= spec["max"]
    assert len(p) == traffic.GRID and p.dtype == np.int64


def test_open_loop_gaps_same_total_every_seed(tmp_path):
    mix = {"loop": "open", "rate_per_s": 4.0,
           "prompt": {"min": 16, "max": 64}, "output": {"min": 4, "max": 8}}
    g = traffic.GRID
    ends = {round(_take(mix, s, g)[-1].t, 9) for s in (3, 4, 5)}
    assert len(ends) == 1
    assert abs(ends.pop() - g / 4.0) < 0.2 * g / 4.0


def test_mix_dropped_in_is_found_by_name(tmp_path):
    (tmp_path / "fresh.json").write_text(json.dumps({
        "loop": "closed", "clients": 2,
        "prompt": {"min": 8, "max": 8}, "output": {"min": 2, "max": 2}}))
    mix = traffic.load_mix("fresh", tmp_path)
    r = _take(mix, 0, 3)
    assert [len(x.prompt) for x in r] == [8, 8, 8]
    with pytest.raises(FileNotFoundError):
        traffic.load_mix("absent", tmp_path)
