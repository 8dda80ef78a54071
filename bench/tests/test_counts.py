"""The operations and bytes the readers count: each kernel's least time
against hand arithmetic, and the counts it rests on against the program's
own accounting (live pages per decode tick, routed pairs of the grouped
GEMM's tile plan) at a smoke size on the CPU."""
import os

import numpy as np
import pytest

from bench import modelflops as MF
from bench.spec import BENCH, reader
from bench.tests.smoke import PEAKS

GRANITE = {"layers": 32, "d_model": 1536, "heads": 24, "kv_heads": 8,
           "head_dim": 64, "vocab": 49155, "experts": 40, "top_k": 8,
           "d_expert": 512, "shared_experts": 0}
DEEPSEEK = {"layers": 7, "d_model": 2048, "heads": 16, "kv_heads": 16,
            "head_dim": 128, "vocab": 102400, "experts": 64, "top_k": 6,
            "d_expert": 1408, "shared_experts": 2}


def module(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"),
        os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_paged_attn_decode_least_time_by_hand():
    m = module("paged_attn_decode_roofline")
    pos = [0, 15, 16, 100]
    flops = 4 * 24 * 64 * (1 + 16 + 17 + 101)
    pages = 1 + 1 + 2 + 7
    byts = pages * 2 * 16 * 8 * 64 * 2 + 4 * 24 * 64 * 6
    want = 32 * max(flops / 197e12, byts / 819e9)
    assert m.least_s(GRANITE, 16, pos, PEAKS) == pytest.approx(want)
    assert byts / 819e9 > flops / 197e12          # decode reads, it is bound
    #                                               by bandwidth


def test_live_pages_match_the_programs_tick_model():
    from repro.kernels.paged_attn import decode_tick_pages
    rng = np.random.default_rng(0)
    t = rng.integers(0, 2048, size=16)
    active = rng.random(16) < 0.7
    live, _ = decode_tick_pages(t, active, 16, 16, 128)
    assert live == sum(int(x) // 16 + 1 for x, a in zip(t, active) if a)


@pytest.mark.parametrize("rows", [1, 16])
def test_gmm_least_time_by_hand(rows):
    m = module("gmm_roofline.decode")
    d, f, E, k = 2048, 1408, 64, 6
    flops = 2 * rows * k * 3 * d * f
    touched = E * (1 - (1 - k / E) ** rows)
    byts = touched * 3 * d * f * 2 + 2 * rows * d * 2
    want = 7 * max(flops / 197e12, byts / 819e9)
    assert m.least_s(DEEPSEEK, rows, PEAKS) == pytest.approx(want)
    if rows == 1:
        assert touched == pytest.approx(k)


def test_gmm_pairs_match_the_programs_tile_plan():
    """The reader counts T x k routed pairs and, under uniform routing,
    E (1 - (1 - k/E)^T) experts touched: the program's tile plan over a
    router's real choice holds exactly those pairs, and the experts it
    touches average the formula."""
    import jax
    from repro.core.routing import token_choice
    from repro.kernels.ops import plan_tile_dispatch
    T, E, k, d = 16, 64, 6, 32
    touched = []
    for seed in range(40):
        kx, kg = jax.random.split(jax.random.PRNGKey(seed))
        x = jax.random.normal(kx, (T, d))
        gate = jax.random.normal(kg, (d, E))
        r = token_choice(x, gate, k)
        plan = plan_tile_dispatch(r.expert_idx.reshape(-1), E, 8)
        counts = np.asarray(plan.counts)[:E]
        assert counts.sum() == T * k
        touched.append(int((counts > 0).sum()))
    assert np.mean(touched) == pytest.approx(E * (1 - (1 - k / E) ** T),
                                             rel=0.05)


def test_active_parameters_by_hand():
    # granite-3.0-3b-a800m: ~800M active parameters below the head
    attn = 1536 * 64 * (2 * 24 + 2 * 8)
    moe = 8 * 3 * 1536 * 512 + 1536 * 40
    assert MF.body_params(GRANITE) == 32 * (attn + moe)
    assert 0.75e9 < MF.body_params(GRANITE) < 0.85e9
    # deepseek: 6 routed + 2 shared experts of width 1408
    attn = 2048 * 128 * 4 * 16
    moe = 8 * 3 * 2048 * 1408 + 2048 * 64
    assert MF.body_params(DEEPSEEK) == 7 * (attn + moe)


def test_step_flops_by_hand():
    from bench.serve import Step
    st = Step(t0=0, t1=1, decode=[9], oneshot=[4], chunks=[(512, 10, True)])
    body, head = MF.body_params(GRANITE), 2 * 1536 * 49155
    att = 4 * 24 * 64 * 32
    want = (2 * body + head + att * 10) \
        + (2 * body * 4 + att * (4 * 5 // 2) + head) \
        + (2 * body * 10 + att * (10 * 512 + 10 * 11 // 2) + head)
    assert MF.step_flops(GRANITE, st) == pytest.approx(want)


def test_mfu_reader_never_reads_zero():
    from types import SimpleNamespace
    run = SimpleNamespace(trace=object(), trace_span=(0.0, 1.0),
                          traced_steps=lambda: [], sizes=GRANITE,
                          peaks=PEAKS)
    assert reader("mfu.decode")(run) is None


@pytest.mark.parametrize("name", ["gmm_roofline.decode",
                                  "paged_attn_decode_roofline", "mfu.decode"])
def test_device_readers_count_every_chips_peak(name):
    """Over four chips the least time, or the peak, is four chips': the
    same trace and ticks read a quarter of what they read over one."""
    import json
    from types import SimpleNamespace

    from bench.profile import Trace
    from bench.serve import Step
    with open(os.path.join(BENCH, "tests", "data", "trace_excerpt.json")) as f:
        tr = Trace.from_dict(json.load(f))
    lo, hi = tr.window()
    steps = [Step(t0=0, t1=1, decode=[300 + 7 * i for i in range(8)])]

    def read(chips):
        return reader(name)(SimpleNamespace(
            trace=tr, traced_steps=lambda: steps, trace_span=(lo, hi),
            sizes=GRANITE, peaks=PEAKS, chips=chips,
            engine={"page_size": 16, "slots": 16, "max_tokens": 4096}))
    one = read(1)
    assert one > 0 and read(4) == pytest.approx(one / 4, rel=1e-12)
