"""The seeded generator: same seed, same stream; stated medians and clips;
every seed the same work, with other tokens."""
import numpy as np
import pytest

from harness import spec, traffic

BIG_SEED = 2 ** 31 + 12345


def reasoning():
    return spec.find_cell("serve-reasoning").traffic


def test_same_seed_same_stream():
    a = traffic.closed_loop(reasoning(), BIG_SEED, 151936)
    b = traffic.closed_loop(reasoning(), BIG_SEED, 151936)
    for x, y in zip(a[0] + a[1], b[0] + b[1]):
        assert x.max_new == y.max_new
        assert np.array_equal(x.prompt, y.prompt)


def test_seeds_share_the_work_in_another_order():
    a = traffic.closed_loop(reasoning(), 1, 151936)
    b = traffic.closed_loop(reasoning(), 2, 151936)
    # the first requests and the queue that refills the slots hold the
    # same sizes in the same order, so every seed's window does the same
    # work; the tokens differ
    sizes = lambda rs: [(len(r.prompt), r.max_new) for r in rs]
    assert sizes(a[0]) == sizes(b[0]) and sizes(a[1]) == sizes(b[1])
    assert len(set(sizes(a[0]))) > 1
    assert not np.array_equal(a[0][0].prompt, b[0][0].prompt)
    assert not np.array_equal(a[1][0].prompt, b[1][0].prompt)


@pytest.mark.parametrize("dist,median", [
    ({"dist": "loguniform", "min": 128, "max": 512}, 256),
    ({"dist": "uniform", "min": 512, "max": 768}, 640)])
def test_medians_and_clips(dist, median):
    xs = traffic.stratified(dist, 401, np.random.default_rng(3))
    assert abs(np.median(xs) - median) <= 1
    assert xs.min() >= dist["min"] and xs.max() <= dist["max"]
    assert xs.min() - dist["min"] <= 1 and dist["max"] - xs.max() <= 1


def test_stratified_draws_are_one_multiset_in_another_order():
    d = {"dist": "loguniform", "min": 128, "max": 512}
    a = traffic.stratified(d, 50, np.random.default_rng(1))
    b = traffic.stratified(d, 50, np.random.default_rng(2))
    assert sorted(a) == sorted(b) and list(a) != list(b)


def test_closed_loop_starts_from_an_aged_loop():
    mix = spec.find_cell("serve-reasoning").traffic
    first, queue = traffic.closed_loop(mix, BIG_SEED, 151936)
    assert len(first) == mix["slots"] and len(queue) == mix["queue"]
    # the first slots finish staggered, their contexts spread over whole
    # lifetimes: prompt plus the output made so far
    assert len({r.max_new for r in first}) == len(first)
    p, o = mix["prompt"], mix["output"]
    for r in first:
        assert p["min"] + o["min"] <= len(r.prompt) + r.max_new \
            <= p["max"] + o["max"]
        assert len(r.prompt) <= traffic.longest_prompt(mix)
    ctx = sorted(len(r.prompt) for r in first)
    other = traffic.closed_loop(mix, 5, 151936)[0]
    assert sorted(len(r.prompt) for r in other) == ctx
    assert ctx[-1] > p["max"] + o["min"] // 2 and ctx[0] < p["max"]
    assert all(o["min"] <= r.max_new <= o["max"] for r in queue)
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in queue)


def test_large_seeds_are_accepted():
    _, j = traffic.seeded(2 ** 40 + 3)
    assert 0 <= j < 2 ** 31
