"""The traffic generator: fixed work per seed, seeds past 32 bits."""
import numpy as np
import pytest

from benchmarks.chip import loadgen

DECODE = {"rate_per_s": 20.0, "drain_s": 0,
          "prompt_len": {"dist": "lognormal", "median": 32, "sigma": 0.5,
                         "min": 8, "max": 128},
          "output_len": {"dist": "lognormal", "median": 128, "sigma": 0.6,
                         "min": 32, "max": 512}}
SEEDS = [0, 1, 2**31 + 11, 2**40 + 3]


@pytest.mark.parametrize("seed", SEEDS)
def test_every_seed_gets_the_same_sizes_and_gaps(seed):
    off0, p0, o0 = loadgen.lm_requests(DECODE, 1000, 10.0, 0)
    off, p, o = loadgen.lm_requests(DECODE, 1000, 10.0, seed)
    assert sorted(len(x) for x in p) == sorted(len(x) for x in p0)
    assert sorted(o) == sorted(o0)
    np.testing.assert_allclose(np.sort(np.diff(off, prepend=0)),
                               np.sort(np.diff(off0, prepend=0)))
    assert off[-1] == pytest.approx(off0[-1])
    assert len(p) == loadgen.n_requests(DECODE, 10.0) == 300


def test_same_seed_same_requests():
    a = loadgen.lm_requests(DECODE, 1000, 5.0, 2**33 + 1)
    b = loadgen.lm_requests(DECODE, 1000, 5.0, 2**33 + 1)
    np.testing.assert_array_equal(a[0], b[0])
    for x, y in zip(a[1], b[1]):
        np.testing.assert_array_equal(x, y)
    c = loadgen.lm_requests(DECODE, 1000, 5.0, 2**33 + 2)
    assert any(len(x) != len(y) or (x != y).any() for x, y in zip(a[1], c[1]))


def test_sizes_follow_their_distributions():
    rng = np.random.default_rng(0)
    s = loadgen.stratified(DECODE["prompt_len"], 2001, rng)
    assert s.min() >= 8 and s.max() <= 128
    assert np.median(s) == 32
    u = loadgen.stratified({"dist": "uniform", "min": 16, "max": 64}, 49,
                           rng)
    assert sorted(u) == list(range(16, 65))
    gaps = np.diff(loadgen.arrival_offsets(20.0, 4000, rng), prepend=0)
    assert gaps.mean() == pytest.approx(1 / 20.0, rel=0.01)


def test_images_are_exact_grid_values():
    off, imgs = loadgen.images({"rate_per_s": 10.0, "pixel_grid": 1024},
                               (4, 4, 3), 1.0, 2**35)
    assert imgs.dtype == np.float32 and imgs.min() >= 0 and imgs.max() < 1
    np.testing.assert_array_equal(imgs * 1024, np.round(imgs * 1024))
    assert len(imgs) == len(off) == 60


def test_sample_keeps_the_must_and_draws_the_rest():
    s = loadgen.sample(list(range(100)), 8, [57], 3)
    assert 57 in s and len(s) == 8 and s == sorted(s)
    assert s == loadgen.sample(list(range(100)), 8, [57], 3)
    assert loadgen.sample([1, 2], 8, [2], 3) == [1, 2]


@pytest.mark.parametrize("n", [16, 37, 300])
def test_balanced_order_spreads_every_stratum_over_the_run(n):
    vals = np.arange(n)
    out = loadgen.balanced(vals, np.random.default_rng(n))
    assert sorted(out) == list(vals)
    nb = -(-n // loadgen.BLOCK)
    strata = vals // nb
    full = (n // loadgen.BLOCK) * loadgen.BLOCK if n % nb == 0 else 0
    for b in range(0, full, loadgen.BLOCK):
        assert sorted(strata[out[b:b + loadgen.BLOCK]]) == \
            list(range(loadgen.BLOCK))
