"""The generator: the same seed gives the same requests; another seed gives
the same work in another order; the window's request count is fixed."""
import numpy as np
import pytest

from bench import traffic as TR
from bench.tests.smoke import TRAFFIC

CHAT = {"arrivals": "poisson", "rate_per_s": 3.0, "lead_in_s": 12,
        "block_requests": 8,
        "prompt_tokens": {"dist": "lognormal", "median": 1020,
                          "sigma": 0.4986, "min": 64, "max": 3072},
        "output_tokens": {"dist": "lognormal", "median": 129, "sigma": 0.992,
                          "min": 8, "max": 1024}}


def key(plan):
    return [(r.prompt.tobytes(), r.max_new, r.due_s, r.in_window)
            for r in plan.requests]


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 5, 3 * 10 ** 12])
def test_same_seed_same_requests(seed):
    assert key(TR.plan(CHAT, seed, 40, 49155)) == \
        key(TR.plan(CHAT, seed, 40, 49155))


def test_seeds_share_the_work_not_the_order():
    a, b = TR.plan(CHAT, 1, 40, 49155), TR.plan(CHAT, 2, 40, 49155)
    for phase in (False, True):
        ra = [r for r in a.requests if r.in_window == phase]
        rb = [r for r in b.requests if r.in_window == phase]
        assert sorted(len(r.prompt) for r in ra) == \
            sorted(len(r.prompt) for r in rb)
        assert sorted(r.max_new for r in ra) == sorted(r.max_new for r in rb)
    assert key(a) != key(b)
    win = [r for r in a.requests if r.in_window]
    assert len(win) == round(3.0 * 40)
    due = np.array([r.due_s for r in win])
    assert due.min() == 12 and due.max() < 52


def test_lengths_follow_the_stated_distribution():
    spec = CHAT["prompt_tokens"]
    x = TR.lengths(spec, 2000, TR.rng_for(0, 1))
    assert x.min() >= 64 and x.max() <= 3072
    assert abs(np.median(x) - 1020) <= 2
    # sigma from the trace's mean over median: the mean comes back
    assert abs(x.mean() / 1155 - 1) < 0.01
    u = TR.lengths({"dist": "uniform", "min": 16, "max": 64}, 490,
                   TR.rng_for(0, 1))
    assert u.min() == 16 and u.max() == 64
    assert np.bincount(u)[16:].min() == 10       # 49 values, 10 of each


def test_arrival_gaps_are_exponential_quantiles():
    off = TR.arrival_offsets(400, 100.0, TR.rng_for(3, 1))
    gaps = np.diff(np.append(off, 100.0))
    assert off[0] == 0 and abs(gaps.sum() - 100.0) < 1e-9
    # exponential: the median gap is ln 2 of the mean
    assert abs(np.median(gaps) / gaps.mean() - np.log(2)) < 0.02


def test_backlog_and_closed_plans():
    bl = TR.plan({**TRAFFIC, "arrivals": "backlog", "backlog_requests": 30},
                 5, 10, 256)
    assert len(bl.requests) == 30 and all(r.due_s == 0 for r in bl.requests)
    cl = TR.plan({**TRAFFIC, "arrivals": "closed", "clients": 3,
                  "closed_requests": 10}, 5, 10, 256)
    assert [len(c) for c in cl.clients] == [4, 3, 3]
    assert all(r.due_s is None for r in cl.requests)


def test_describe():
    assert TR.describe([1, 2, 3]).startswith("n=3 min=1 p50=2")


@pytest.mark.parametrize("n,block", [(40, 8), (24, 4), (9, 1)])
def test_every_block_gets_every_stratum(n, block):
    """Dealt in blocks, each block holds one value of every stratum of the
    sorted values (stratum j: values j*m .. (j+1)*m - 1 of m blocks), so
    each stretch gets the same spread; the values are the same whatever the
    seed, the order is not."""
    v = np.arange(n) * 10
    m = n // block
    outs = [TR.deal(v, block, TR.rng_for(seed, 1)) for seed in (1, 2)]
    for out in outs:
        assert sorted(out) == list(v)
        for i in range(0, n, block):
            assert sorted(out[i:i + block] // (10 * m)) == list(range(block))
    assert n == 9 or list(outs[0]) != list(outs[1])


@pytest.mark.parametrize("n", [1, 7, 25])
def test_a_short_last_stratum_keeps_every_value(n):
    out = TR.deal(np.arange(n), 8, TR.rng_for(4, 1))
    assert sorted(out) == list(range(n))


def test_a_window_quarter_gets_its_share_of_the_work():
    plan = TR.plan(CHAT, 9, 40, 49155)
    win = sorted((r for r in plan.requests if r.in_window),
                 key=lambda r: r.due_s)
    total = sum(len(r.prompt) for r in win)
    for i in range(0, len(win), 8):
        part = sum(len(r.prompt) for r in win[i:i + 8])
        assert 0.5 < part / (total * len(win[i:i + 8]) / len(win)) < 1.6
