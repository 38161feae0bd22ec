"""The stratified generator: the work in a window does not depend on the
seed, only its order does."""

import numpy as np

from bench import loadgen
from bench.tests.conftest import TINY_MIXES

CHAT = dict(kind="open", rate_per_s=2.5, lead_s=6.0, greedy_every=4,
            sampling={"temperature": 0.7, "top_p": 0.9},
            prompt={"dist": "lognormal", "median": 200, "sigma": 0.8,
                    "min": 16, "max": 512},
            output={"dist": "lognormal", "median": 128, "sigma": 0.6,
                    "min": 16, "max": 256})


def test_open_work_equal_across_seeds_and_order_differs():
    a = loadgen.Traffic(CHAT, 2 ** 33 + 7, 1000).schedule(45)
    b = loadgen.Traffic(CHAT, 11, 1000).schedule(45)
    for key in (lambda s: len(s.prompt), lambda s: s.max_new_tokens,
                lambda s: s.greedy):
        for inside in (True, False):
            ka = sorted(key(s) for s in a if s.in_window == inside)
            kb = sorted(key(s) for s in b if s.in_window == inside)
            assert ka == kb
    assert [len(s.prompt) for s in a] != [len(s.prompt) for s in b]
    win = [s for s in a if s.in_window]
    assert len(win) == round(2.5 * 45)
    assert all(0 <= s.due < 45 for s in win)
    assert all(-6 <= s.due < 0 for s in a if not s.in_window)
    dues = [s.due for s in a]
    assert dues == sorted(dues)
    assert sum(s.greedy for s in win) == -(-len(win) // 4)


def test_same_seed_same_requests():
    a = loadgen.Traffic(CHAT, 5, 1000).schedule(20)
    b = loadgen.Traffic(CHAT, 5, 1000).schedule(20)
    assert all(np.array_equal(x.prompt, y.prompt) and x.due == y.due
               and x.sample_seed == y.sample_seed for x, y in zip(a, b))


def test_gaps_fill_the_window():
    g = loadgen.exp_gaps(100, 40.0)
    assert abs(g.sum() - 40.0) < 1e-9 and (g > 0).all()


def test_quantiles_follow_the_distribution():
    q = loadgen.quantiles(CHAT["prompt"], 1001)
    assert q[500] == 200 and q.min() >= 16 and q.max() <= 512
    assert (np.diff(q) >= 0).all()


def test_lengths_cover_every_draw():
    t = loadgen.Traffic(CHAT, 3, 1000)
    want = t.lengths(45)
    assert {len(s.prompt) for s in t.schedule(45)} <= want
    mix = TINY_MIXES["tiny_closed"]
    t = loadgen.Traffic(mix, 3, 256)
    it = t.backlog()
    drawn = [next(it) for _ in range(3 * mix["pool"])]
    assert {len(s.prompt) for s in drawn} <= t.lengths()
    first = sorted(len(s.prompt) for s in drawn[:mix["pool"]])
    second = sorted(len(s.prompt) for s in drawn[mix["pool"]:2 * mix["pool"]])
    assert first == second


def test_block_order_spreads_every_stretch_and_follows_the_seed():
    n, block = 41, 2
    a = loadgen.seeded_order(n, np.random.default_rng(1), block, 0.618)
    b = loadgen.seeded_order(n, np.random.default_rng(2), block, 0.618)
    assert sorted(a) == list(range(n)) and sorted(b) == list(range(n))
    assert list(a) != list(b)
    # the seed only reorders within a block: each block holds the same set
    for i in range(0, n, block):
        assert set(a[i:i + block]) == set(b[i:i + block])
    # any ten consecutive positions reach both ends of the range
    for i in range(n - 10):
        assert min(a[i:i + 10]) < n // 4 and max(a[i:i + 10]) >= 3 * n // 4


def test_blocked_mix_holds_an_even_load_for_every_seed():
    mix = dict(CHAT, order_block=2)
    firsts = []
    for seed in range(8):
        win = [s for s in loadgen.Traffic(mix, seed, 1000).schedule(45)
               if s.in_window]
        firsts.append(sum(s.max_new_tokens for s in win[:len(win) // 2]))
    assert (max(firsts) - min(firsts)) / np.mean(firsts) < 0.1


def _window_dues(mix, seed):
    return [s.due for s in loadgen.Traffic(mix, seed, 1000).schedule(51)
            if s.in_window]


def test_fixed_arrival_pattern_is_the_same_for_every_seed():
    mix = dict(CHAT, rate_per_s=0.8, arrival_seed=0, order_block=2)
    assert _window_dues(mix, 3) == _window_dues(mix, 2 ** 33 + 1)
    assert _window_dues(mix, 3) != _window_dues(dict(mix, arrival_seed=1), 3)
    free = dict(CHAT, rate_per_s=0.8)
    assert _window_dues(free, 3) != _window_dues(free, 4)


def test_arrivals_burst_as_a_poisson_process_does():
    """Counts of arrivals in tenths of the window scatter about as widely
    as a Poisson process's (index of dispersion near 1), not evenly."""
    ratios = []
    for a in range(40):
        dues = _window_dues(dict(CHAT, rate_per_s=0.8, arrival_seed=a), 1)
        counts = np.histogram(dues, bins=np.linspace(0, 51, 11))[0]
        ratios.append(counts.var() / counts.mean())
    assert 0.6 < np.mean(ratios) < 1.4
