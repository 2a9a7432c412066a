"""The wall-clock load: seeded, at the asked rate, with the program's mix."""
import numpy as np
import pytest

from perfbench.loadgen import WallLoad, zipf_probs


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_same_seed_same_due_times():
    a = WallLoad(rate=80.0, n_domains=256, seed=(1 << 33) + 7)
    b = WallLoad(rate=80.0, n_domains=256, seed=(1 << 33) + 7)
    c = WallLoad(rate=80.0, n_domains=256, seed=(1 << 33) + 8)
    n = a.schedule(30.0)
    assert b.schedule(30.0) == n
    a.start(0.0), b.start(0.0), c.start(0.0)
    assert np.array_equal(a.due(0, n), b.due(0, n))
    assert np.array_equal(a.queries(0, n)[0], b.queries(0, n)[0])
    m = c.schedule(30.0)
    assert not np.array_equal(a.due(0, min(n, m)), c.due(0, min(n, m)))


@pytest.mark.parametrize("rate", [5.0, 300.0, 2.5])
def test_realised_rate_is_the_asked_rate_within_poisson_spread(rate):
    for seed in range(3):
        load = WallLoad(rate=rate, n_domains=256, seed=seed)
        n = load.schedule(200.0)
        assert abs(n - rate * 200.0) <= 4 * np.sqrt(rate * 200.0) + 1, \
            (n, rate)


def test_seconds_are_poisson_and_seeds_reorder_the_same_blocks():
    """A second's count is Poisson (its variance is its mean); every seed
    offers the same counts in each block of seconds, in its own order."""
    a = WallLoad(rate=300.0, n_domains=256, seed=(1 << 33) + 1, block_s=4)
    b = WallLoad(rate=300.0, n_domains=256, seed=(1 << 33) + 2, block_s=4)
    ca = np.concatenate([a.counts(k) for k in range(500)])
    cb = np.concatenate([b.counts(k) for k in range(500)])
    assert 0.85 < ca.var() / ca.mean() < 1.15
    assert abs(ca.mean() - 300.0) < 4 * np.sqrt(300.0 / len(ca))
    assert np.array_equal(np.sort(ca.reshape(-1, 4), 1),
                          np.sort(cb.reshape(-1, 4), 1))
    assert not np.array_equal(ca, cb)
    na, nb = a.schedule(40.0), b.schedule(40.0)
    assert na == nb == ca[:40].sum()


def test_topic_mix_is_the_programs():
    from repro.configs.base import CrawlConfig
    from repro.serve.load import QueryLoad
    cfg = CrawlConfig()
    assert np.allclose(zipf_probs(cfg.n_domains, 1.1),
                       QueryLoad(cfg, zipf_q=1.1)._probs)
    load = WallLoad(rate=2000.0, n_domains=256, seed=3, zipf_q=1.1)
    n = load.schedule(50.0)
    _, dom = load.queries(0, n)
    share = np.bincount(dom, minlength=256) / n
    p = zipf_probs(256, 1.1)
    assert np.all(np.abs(share - p) < 5 * np.sqrt(p * (1 - p) / n) + 1e-9)


def test_take_hands_out_what_is_due_on_the_wall_clock():
    clock = Clock()
    load = WallLoad(rate=50.0, n_domains=16, seed=9, clock=clock)
    load.add_warm(4)
    warm = load.take(0, 3.0)
    assert len(warm) == 4 and warm.cursor == 0
    assert len(load.take(0, 3.0)) == 0          # no clock yet, no queries
    load.start(100.0)
    clock.t = 102.5
    first = load.take(0, 7.0)
    assert len(first) == load.schedule(2.5)
    assert np.all(load.due(0, first.cursor) <= 102.5)
    assert np.all(first.time == 7.0)            # stamped with the session's
    clock.t = 104.0
    second = load.take(first.cursor, 8.0)
    assert second.cursor == load.schedule(4.0)
    assert np.all(load.due(first.cursor, second.cursor) > 102.5)
    assert [t[0] for t in load.takes] == [102.5, 104.0]
