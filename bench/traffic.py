"""The one traffic generator. A traffic mix is a JSON file of parameters
under `bench/traffic/`; this module turns it and a seed into requests.

Every seed gets the same work in another order: lengths are the quantiles
(i + 0.5) / n of the stated distribution and open-loop gaps are the same
quantiles of an exponential distribution, scaled to span their phase
exactly, and the seed only orders them and draws the token ids. So runs
with different seeds differ in order and routing, not in the amount of
work, and the number of requests due in the window is fixed.

The order is dealt in blocks of `block_requests` consecutive requests
(default: one block per phase): the phase's sorted values are cut into
strata of one value per block, and each block takes one value of every
stratum, in an order the seed draws. So every stretch of the window gets
the same spread of prompt lengths, output lengths and gaps, long and short
alike, and no seed piles the long requests or the short gaps into one
part of it.

Arrival kinds:
  poisson  open loop at `rate_per_s`: a lead-in of `lead_in_s` seconds of
           load, then the measured window; each request is due at its time
           whatever the engine is doing.
  backlog  offline batch: `backlog_requests` are due at load start, more
           than the window can drain; the window opens `lead_in_s` later.
  closed   `clients` callers, each sending its next request the moment the
           previous one ends; the window opens once every client's first
           request has its first token (their caches are filled in set-up).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import NormalDist

import numpy as np

TRAFFIC_STREAM = 1      # the seed's stream for traffic (weights use another)


@dataclass
class Planned:
    """One request of the plan. `due_s` is relative to load start; None
    for a closed-loop request, which is due when its client's previous
    request ends."""
    index: int
    prompt: np.ndarray
    max_new: int
    due_s: float | None
    client: int = -1
    in_window: bool = False


@dataclass
class Plan:
    kind: str
    requests: list
    lead_in_s: float
    clients: list = field(default_factory=list)   # closed: request queues


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def deal(values: np.ndarray, block: int,
         rng: np.random.Generator) -> np.ndarray:
    """`values` in an order drawn by `rng`, as m = ceil(n / block)
    consecutive blocks of at most `block` entries, each holding one value
    of every stratum of the sorted values (stratum j: the sorted values
    j*m .. (j+1)*m - 1; the last stratum may leave some blocks out)."""
    v = np.sort(np.asarray(values))
    n = len(v)
    m = -(-n // max(1, min(block, n))) if n else 0
    blocks = [[] for _ in range(m)]
    for j in range(0, n, m):
        for b, x in zip(rng.permutation(m), v[j:j + m]):
            blocks[b].append(x)
    return np.concatenate([rng.permutation(np.asarray(b, v.dtype))
                           for b in blocks]) if n else v


def lengths(spec: dict, n: int, rng: np.random.Generator,
            block: int = 0) -> np.ndarray:
    """n lengths at the distribution's stratified quantiles, dealt in
    blocks of `block` (0: one block)."""
    q = quantiles(n)
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(v) for v in q])
        x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = lo + q * (hi - lo + 1)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    out = np.clip(np.floor(x), lo, hi).astype(np.int64)
    return deal(out, block or n, rng)


def arrival_offsets(n: int, span_s: float, rng: np.random.Generator,
                    block: int = 0) -> np.ndarray:
    """n open-loop arrivals over [0, span_s): exponential gaps at the
    stratified quantiles, dealt in blocks of `block` (0: one block),
    scaled to sum to span_s."""
    gaps = deal(-np.log1p(-quantiles(n)), block or n, rng)
    gaps *= span_s / gaps.sum()
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def plan(traffic: dict, seed: int, seconds: float, vocab: int) -> Plan:
    rng = rng_for(seed, TRAFFIC_STREAM)
    kind = traffic["arrivals"]
    lead = float(traffic.get("lead_in_s", 0.0))
    if kind == "poisson":
        rate = float(traffic["rate_per_s"])
        phases = [(0.0, lead, False), (lead, seconds, True)]
        counts = [max(1, round(rate * span)) for _, span, _ in phases]
    elif kind == "backlog":
        counts = [int(traffic["backlog_requests"])]
    elif kind == "closed":
        counts = [int(traffic["closed_requests"])]
    else:
        raise ValueError(f"unknown arrival kind {kind!r}")
    n = sum(counts)
    block = int(traffic.get("block_requests", 0))
    # each phase draws its own stratified set, so the window's work is fixed
    p_len = np.concatenate([lengths(traffic["prompt_tokens"], c, rng, block)
                            for c in counts])
    o_len = np.concatenate([lengths(traffic["output_tokens"], c, rng, block)
                            for c in counts])
    if kind == "poisson":
        due = np.concatenate([start + arrival_offsets(c, span, rng, block)
                              for (start, span, _), c in zip(phases, counts)])
        win = np.concatenate([np.full(c, w)
                              for (_, _, w), c in zip(phases, counts)])
    elif kind == "backlog":
        due, win = np.zeros(n), np.ones(n, bool)
    else:
        due, win = [None] * n, np.ones(n, bool)
    reqs = [Planned(index=i,
                    prompt=rng.integers(0, vocab, size=int(p_len[i]),
                                        dtype=np.int32),
                    max_new=int(o_len[i]),
                    due_s=None if due[i] is None else float(due[i]),
                    in_window=bool(win[i]))
            for i in range(n)]
    clients = []
    if kind == "closed":
        c = int(traffic["clients"])
        for r in reqs:
            r.client = r.index % c
        clients = [[r for r in reqs if r.client == j] for j in range(c)]
    return Plan(kind=kind, requests=reqs, lead_in_s=lead, clients=clients)


def describe(values) -> str:
    v = np.asarray(values, np.float64)
    if v.size == 0:
        return "n=0"
    return (f"n={v.size} min={v.min():g} p50={np.percentile(v, 50):g} "
            f"p90={np.percentile(v, 90):g} max={v.max():g} "
            f"mean={v.mean():g}")
