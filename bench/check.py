"""The comparison that decides `correct`.

After the window has closed and the engine's state is freed, a sample of
the requests the timed path finished, drawn from the seed and holding the
longest one, goes through the plain reference in float32: prompt and served
tokens together. Where the finished requests hold fewer served tokens than
the sample asks for (a cell whose requests outlast the window), the
requests still being served at the close fill it with the tokens they had
served by then, the longest of them first. Each served token was the program's greedy choice, so the
reference should rank it at or near its best; the numbers read are how far,
in logits, the served tokens lie below the reference's best at their
positions, and the configuration's `limits` name those compared. Requests
that failed or were refused, requests that ended with the wrong length and
decode ticks the engine had to retry are compared too, each against 0.
"""
from __future__ import annotations

import numpy as np

from bench.traffic import rng_for

SAMPLE_TOKENS = 400     # served tokens to compare, at least (the longest
MAX_SEQUENCES = 8       # request always, then a seeded draw of the rest)
SAMPLE_STREAM = 3
BAD = ("FAILED", "TIMEOUT", "CANCELLED")
NUMBERS = ("logit_gap", "mean_logit_gap")


def finished(run) -> list:
    return [r for r in run.recs if r.req is not None
            and getattr(r.req.status, "value", r.req.status) == "DONE"]


def in_flight(run) -> list:
    """Requests still being served at the close, with a token served."""
    return [r for r in run.recs if r.req is not None and r.req.tokens
            and getattr(r.req.status, "value", r.req.status)
            not in ("DONE",) + BAD]


def sample(run, seed: int) -> list:
    """The finished requests' longest, then a seeded draw of the rest, until
    SAMPLE_TOKENS served tokens or MAX_SEQUENCES requests; short of that,
    the requests in flight at the close alike."""
    rng = rng_for(seed, SAMPLE_STREAM)
    chosen, served = [], 0
    for pool in (finished(run), in_flight(run)):
        if not pool or served >= SAMPLE_TOKENS or \
                len(chosen) >= MAX_SEQUENCES:
            continue
        longest = max(pool, key=lambda r: (r.prompt_len + len(r.req.tokens),
                                           -r.rid))
        chosen.append(longest)
        served += len(longest.req.tokens)
        for i in rng.permutation(len(pool)):
            if served >= SAMPLE_TOKENS or len(chosen) >= MAX_SEQUENCES:
                break
            if pool[i] is not longest:
                chosen.append(pool[i])
                served += len(pool[i].req.tokens)
    return chosen


def gaps(chosen, weights, sz, ref, *, control: bool = False) -> np.ndarray:
    """The gap of every served token of the chosen requests (with
    `control`, of the tokens the fp8 forward would have served)."""
    length = ref.bucket(max(r.prompt_len + len(r.req.tokens) for r in chosen))
    return np.concatenate([
        ref.gaps(weights, sz, r.planned.prompt,
                 np.asarray(r.req.tokens, np.int32), length=length,
                 control=control)
        for r in chosen])


def gap_numbers(g: np.ndarray) -> dict:
    """The widest gap and the mean gap over every served token. A
    configuration's `limits` name the ones compared: the mean, since the
    widest is set by the one worst near-tie (a routing flip moves it as far
    in bfloat16 as in the float8 control)."""
    return dict(zip(NUMBERS, (float(g.max()), float(g.mean()))))


def compare(numbers: dict, limits: dict, counts: dict) -> dict:
    """Each compared number beside its limit: the gaps the configuration's
    `limits` name, and the fault counts against 0."""
    out = {k: [numbers[k], lim] for k, lim in limits.items()}
    out.update({k: [v, 0] for k, v in counts.items()})
    return out


def passes(compared: dict) -> bool:
    return all(v is not None and v <= lim for v, lim in compared.values())


def failed(r) -> bool:
    """Refused at submission, or ended in a terminal status but DONE."""
    return r.rejected is not None or (
        r.req is not None
        and getattr(r.req.status, "value", r.req.status) in BAD)


def faults(run, tick_retries: int) -> dict:
    """Counts held to 0: requests that failed or were refused, requests that
    ended DONE with the wrong number of tokens, and retried decode ticks."""
    bad = sum(failed(r) for r in run.recs)
    short = sum(len(r.req.tokens) != r.planned.max_new
                for r in finished(run))
    return {"failed_requests": bad, "wrong_length": short,
            "tick_retries": tick_retries}
