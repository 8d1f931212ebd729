"""The traffic generator: deterministic per seed, and every seed gets the
same lengths and gaps in the same order, with its own token ids."""

import numpy as np
import pytest

from bench import model, traffic


def gen(mix, seed, seconds=30.0):
    return traffic.generate(mix, 1000, seconds,
                            model.rng(seed, model.STREAM_TRAFFIC))


@pytest.mark.parametrize("name", ["batch", "chat"])
def test_same_seed_same_requests(name):
    mix = traffic.load(name)
    a, b = gen(mix, 2**33 + 5), gen(mix, 2**33 + 5)
    assert len(a) == len(b) == traffic.count(mix, 30.0)
    for x, y in zip(a, b):
        assert x.arrival_s == y.arrival_s and x.max_new == y.max_new
        assert np.array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", ["batch", "chat"])
def test_seeds_share_the_sizes_and_their_order(name):
    mix = traffic.load(name)
    a, b = gen(mix, 1), gen(mix, 2)
    assert [r.max_new for r in a] == [r.max_new for r in b]
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert [r.arrival_s for r in a] == [r.arrival_s for r in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert len(set(r.max_new for r in a)) > 3  # lengths spread, shuffled
    assert [r.max_new for r in a] != sorted(r.max_new for r in a)
    for r in a:
        assert mix["prompt"]["min"] <= len(r.prompt) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= r.max_new <= mix["output"]["max"]
        assert ((r.prompt >= 2) & (r.prompt < 1000)).all()


def test_open_loop_arrivals_keep_the_rate_and_cover_the_window():
    mix = traffic.load("chat")
    for seed in (1, 2):
        t = np.array([r.arrival_s for r in gen(mix, seed)])
        assert (np.diff(t) >= 0).all()
        assert t[-1] >= 30.0
        assert len(t) / t[-1] == pytest.approx(mix["rate"], rel=0.1)


def test_bursts_arrive_together_at_the_same_mean_rate():
    mix = dict(traffic.load("chat"), burst=4)
    t = np.array([r.arrival_s for r in gen(mix, 3, seconds=200.0)])
    assert (t[0:4] == t[0]).all() and t[4] > t[0]
    assert len(t) / t[-1] == pytest.approx(mix["rate"], rel=0.15)


def test_closed_loop_offers_every_request_at_once():
    reqs = gen(traffic.load("batch"), 4)
    assert all(r.arrival_s is None for r in reqs)
