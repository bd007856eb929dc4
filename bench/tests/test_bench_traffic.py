"""The traffic generator: deterministic in the seed, exact in its ratios,
the same work for every seed."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import traffic  # noqa: E402

MIXED = {"mode": "online", "rate_per_s": 200.0,
         "kinds": {"silent": 1, "patch": 1, "dense": 1}}
OFFLINE = {"mode": "offline", "pool": 30, "kinds": {"silent": 1, "patch": 1}}


@pytest.mark.parametrize("mix", [MIXED, OFFLINE], ids=["online", "offline"])
def test_same_seed_same_requests(mix):
    a = traffic.generate(mix, 2**31 + 5, 1.5, 16, 3)
    b = traffic.generate(mix, 2**31 + 5, 1.5, 16, 3)
    c = traffic.generate(mix, 2**31 + 6, 1.5, 16, 3)
    assert np.array_equal(a.images, b.images) and a.kinds == b.kinds
    assert np.array_equal(a.due_s, b.due_s)
    assert not np.array_equal(a.images, c.images)


def test_every_seed_gets_the_same_work_in_another_order():
    a = traffic.generate(MIXED, 1, 2.0, 8, 3)
    b = traffic.generate(MIXED, 2, 2.0, 8, 3)
    assert len(a.kinds) == len(b.kinds) == 400
    assert sorted(a.kinds) == sorted(b.kinds) and a.kinds != b.kinds
    assert np.allclose(np.sort(np.diff(a.due_s, prepend=0.0)),
                       np.sort(np.diff(b.due_s, prepend=0.0)))


@pytest.mark.parametrize("weights,n,expect", [
    ({"silent": 1, "patch": 1, "dense": 1}, 300, {"silent": 100, "patch": 100, "dense": 100}),
    ({"silent": 1, "patch": 1}, 7, {"patch": 4, "silent": 3}),
    ({"dense": 1}, 5, {"dense": 5}),
])
def test_kinds_are_dealt_in_exact_proportion(weights, n, expect):
    dealt = traffic.deal(weights, n)
    assert {k: dealt.count(k) for k in expect} == expect and len(dealt) == n


def test_poisson_gaps_have_the_rate_asked_for():
    gaps = traffic.exponential_gaps(4000, 250.0)
    assert abs(gaps.mean() * 250.0 - 1.0) < 0.01
    assert abs(np.median(gaps) * 250.0 - np.log(2)) < 0.01


def test_image_kinds_look_as_named():
    rng = np.random.default_rng(0)
    silent = traffic.make_image("silent", rng, 32, 3)
    patch = traffic.make_image("patch", rng, 32, 3)
    dense = traffic.make_image("dense", rng, 32, 3)
    assert silent.max() <= 0.02 and silent.dtype == np.float32
    assert patch[8:].max() == 0 and patch[:8, :8].min() >= 0.5
    assert dense.mean() > 0.4
    with pytest.raises(ValueError):
        traffic.make_image("stripes", rng, 32, 3)
