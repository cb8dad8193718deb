"""The traffic generator: one seed gives one request sequence; every
seed draws the same catalog of sizes in another order; lengths follow
the clipped distributions of the mix file."""

from collections import Counter

import numpy as np
import pytest

from bench import traffic

MIX = {"loop": "open", "rate": 4.0, "requests": 64,
       "prompt": {"dist": "lognormal", "median": 192, "sigma": 0.8,
                  "min": 16, "max": 768},
       "output": {"dist": "lognormal", "median": 96, "sigma": 0.8,
                  "min": 8, "max": 256}}


def draw(mix, seed, n, vocab=1000):
    g = traffic.Generator(mix, vocab, seed)
    return [g.next() for _ in range(n)]


def test_same_seed_same_requests():
    a, b = draw(MIX, 2**33 + 7, 80), draw(MIX, 2**33 + 7, 80)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.offset_s) == (y.max_new, y.offset_s)


def test_seeds_permute_one_catalog():
    n = MIX["requests"]
    a, b = draw(MIX, 1, n), draw(MIX, 2, n)
    sizes = [Counter((len(r.prompt), r.max_new) for r in s) for s in (a, b)]
    assert sizes[0] == sizes[1]
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    # the arrival gaps are one set too: after the whole catalog the
    # clock stands at the same time, reached in another order
    a1, b1 = draw(MIX, 1, n + 1), draw(MIX, 2, n + 1)
    assert a1[n].offset_s == pytest.approx(b1[n].offset_s, rel=1e-12)
    assert a1[n // 2].offset_s != b1[n // 2].offset_s


def test_tokens_differ_between_seeds():
    a, b = draw(MIX, 1, 4), draw(MIX, 2, 4)
    assert any(len(x.prompt) != len(y.prompt)
               or not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a, b))


@pytest.mark.parametrize("dist", [
    {"dist": "lognormal", "median": 256, "sigma": 0.6, "min": 32,
     "max": 512},
    {"dist": "lognormal", "median": 96, "sigma": 0.8, "min": 8, "max": 256},
])
def test_clipped_lognormal(dist):
    x = traffic.lengths(dist, 20000, np.random.default_rng(0))
    assert x.min() >= dist["min"] and x.max() <= dist["max"]
    assert abs(np.median(x) - dist["median"]) <= 0.05 * dist["median"]
    # both clips are reached: the tails are cut, not absent
    assert (x == dist["min"]).any() and (x == dist["max"]).any()
    # heavy right tail: the mean lies above the median
    assert x.mean() > np.median(x)


def test_uniform_lengths():
    x = traffic.lengths({"dist": "uniform", "min": 8, "max": 32}, 5000,
                        np.random.default_rng(0))
    assert x.min() == 8 and x.max() == 32
    assert set(np.unique(x)) == set(range(8, 33))


def test_poisson_arrivals():
    mix = dict(MIX, requests=4000)
    rs = draw(mix, 3, 4000)
    offs = np.array([r.offset_s for r in rs])
    assert offs[0] == 0.0 and np.all(np.diff(offs) > 0)
    gaps = np.diff(offs)
    assert abs(gaps.mean() - 1 / mix["rate"]) < 0.05 / mix["rate"]
    # exponential: the spread equals the mean
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.1


def test_closed_loop_has_no_schedule():
    mix = {"loop": "closed", "clients": 8, "requests": 24,
           "prompt": MIX["prompt"], "output": MIX["output"]}
    assert all(r.offset_s is None for r in draw(mix, 5, 30))


def test_stagger_keeps_a_share_of_the_output():
    r = traffic.Request(idx=0, prompt=np.zeros(4, np.int32), max_new=400,
                        offset_s=None)
    kept = [traffic.staggered(r, c, 8).max_new for c in range(8)]
    assert kept == [25, 75, 125, 175, 225, 275, 325, 375]
    tiny = traffic.Request(idx=0, prompt=r.prompt, max_new=1, offset_s=None)
    assert traffic.staggered(tiny, 0, 8).max_new == 1


def test_catalog_cycles_past_its_end():
    n = MIX["requests"]
    rs = draw(MIX, 9, 2 * n)
    first = Counter((len(r.prompt), r.max_new) for r in rs[:n])
    second = Counter((len(r.prompt), r.max_new) for r in rs[n:])
    assert first == second


def test_unknown_distribution_is_refused():
    with pytest.raises(ValueError):
        traffic.lengths({"dist": "zipf", "min": 1, "max": 2}, 3,
                        np.random.default_rng(0))


def test_ramp_and_window_are_permuted_apart():
    mix = dict(MIX, ramp_requests=20)
    n, r = mix["requests"], mix["ramp_requests"]
    ramp = traffic.ramp_s(mix)
    runs = [draw(mix, s, n) for s in (11, 12)]
    for rs in runs:
        # the window's first request comes exactly when the ramp ends
        assert rs[r].offset_s == pytest.approx(ramp, rel=1e-12)
        assert all(q.offset_s < ramp for q in rs[:r])
    parts = [[Counter((len(q.prompt), q.max_new) for q in rs[a:b])
              for a, b in ((0, r), (r, n))] for rs in runs]
    assert parts[0] == parts[1]
    assert [len(q.prompt) for q in runs[0][r:]] != \
        [len(q.prompt) for q in runs[1][r:]]
