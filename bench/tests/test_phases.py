"""The readers of the program's own spans, counters and scopes
(bench/phases.py, bench/metrics/queue_wait_p50_ms.py, prefill_p50_ms.py,
attn_dead_steps.decode.py):
hand-made traces check the arithmetic, the recorded chip excerpt the names,
and the existing readers read the existing excerpt as they did."""
import json
import os
from types import SimpleNamespace

import pytest

from bench import phases as PH
from bench import serve as SV
from bench.profile import HOST_SPANS, Trace
from bench.spec import load_cell, reader
from bench.tests.smoke import PEAKS, smoke_of

HERE = os.path.dirname(os.path.abspath(__file__))


def _path(scope, prim):
    return f"jit(_decode_step)/while/body/{scope}/{prim}"


# ns: one tick [100, 1100): engine.step holds expire, admit (with a
# prefill wait), decode dispatch (with its wait) and commit; the decode
# program [300, 900) holds a `while` over a kv read, attention, two parts
# of the MoE layer and a kv write, and an unscoped copy after the loop.
HAND = {
    "ops": [("while.1", 300, 500), ("dynamic-slice.2", 310, 40),
            ("_paged_attn_decode.3", 360, 100), ("_gmm_swiglu.4", 470, 150),
            ("fusion.5", 630, 50), ("dynamic-update-slice.6", 700, 60),
            ("copy.7", 820, 60)],
    "modules": [("jit__decode_step(1)", 300, 600)],
    "host": [("bench.window", 0, 1400), ("step", 90, 1020),
             ("observe", 1120, 50), ("wait", 1180, 200)],
    "spans": [
        ("engine.step", 100, 1000,
         {"decode_rows": 3, "live_pages": 12, "grid_pages": 64}),
        ("engine.expire", 110, 10, {}),
        ("engine.admit", 130, 150, {}),
        ("engine.prefill.wait", 150, 100, {}),
        ("engine.decode.dispatch", 290, 700, {}),
        ("engine.decode.wait", 320, 660, {}),
        ("engine.commit", 1000, 90, {}),
    ],
    "scopes": {"dynamic-slice.2": _path("kv_read", "dynamic_slice"),
               "_paged_attn_decode.3": _path("attn", "pallas_call"),
               "_gmm_swiglu.4": _path("moe/experts", "pallas_call"),
               "fusion.5": _path("moe/combine", "scatter-add"),
               "dynamic-update-slice.6": _path("kv_write", "dus"),
               "copy.7": "jit(_decode_step)/copy",
               "while.1": "jit(_decode_step)/while"},
}


def hand():
    return PH.ProgramTrace.from_dict(HAND)


def test_tick_host_time_leaves_out_the_waits():
    # 1000 ns of tick less 100 of prefill wait and 660 of decode wait
    assert PH.tick_host_ms(hand()) == pytest.approx(240e-6)


def test_dead_steps_from_the_tick_counters():
    assert PH.attn_dead_steps(hand()) == pytest.approx(100 * (1 - 12 / 64))


def test_scope_times_partition_the_decode_program():
    got = PH.decode_scope_ms(hand())
    # the `while` owns the 500 ns its five inner ops leave: 100
    assert got == pytest.approx({
        "kv": 100e-6, "attn": 100e-6, "moe": 200e-6,
        "moe/experts": 150e-6, "moe/combine": 50e-6, "unscoped": 160e-6})
    assert sum(v for k, v in got.items() if "/" not in k) == \
        pytest.approx(sum(d for _, _, d in HAND["modules"]) / 1e6 - 40e-6)


def test_idle_goes_to_the_innermost_span():
    got = dict(PH.idle_phases(hand(), n=20))
    # device busy [300, 800) and [820, 880); idle [0,300), [800,820),
    # [880,1400)
    assert got == pytest.approx({
        "other": 130e-9,            # [0,90), [1110,1120), [1170,1180), ..
        "step": 20e-9,              # [90,100), [1100,1110)
        "engine.step": 50e-9,       # between its phases
        "engine.expire": 10e-9,
        "engine.admit": 50e-9,
        "engine.prefill.wait": 100e-9,
        "engine.decode.dispatch": 20e-9,     # [290,300), [980,990)
        "engine.decode.wait": 120e-9,        # [800,820), [880,980)
        "engine.commit": 90e-9,
        "observe": 50e-9, "wait": 200e-9})
    assert sum(got.values()) == pytest.approx(
        Trace.from_dict(HAND).window_s() - Trace.from_dict(HAND).busy_s())


def test_readers_find_nothing_in_a_trace_without_the_program_spans():
    bare = PH.ProgramTrace.from_dict({k: HAND[k]
                                      for k in ("ops", "modules", "host")})
    assert PH.tick_host_ms(bare) is None
    assert PH.attn_dead_steps(bare) is None
    assert PH.decode_scope_ms(bare) is None
    # idle pieces go to the harness's spans alone
    assert dict(PH.idle_phases(bare)) == pytest.approx({
        "other": 130e-9, "step": 460e-9, "observe": 50e-9, "wait": 200e-9})


HLO = """HloModule jit__decode_step, is_scheduled=true

%fused_computation (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %tanh.0 = f32[8]{0} tanh(%param_0.1), metadata={op_type="tanh" op_name="jit(_decode_step)/while/body/moe/router/tanh"}
}

%fused_pair (param_0.2: f32[8]) -> (f32[8], f32[8]) {
  %param_0.2 = f32[8]{0} parameter(0)
  %neg.1 = f32[8]{0} negate(%param_0.2), metadata={op_name="jit(_decode_step)/while/body/kv_write/neg"}
  %abs.1 = f32[8]{0} abs(%param_0.2)
  ROOT %tuple.1 = (f32[8]{0}, f32[8]{0}) tuple(%neg.1, %abs.1)
}

ENTRY %main.2 (x.1: f32[8], y: (f32[8], /*index=1*/f32[8])) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %tanh_fusion = f32[8]{0} fusion(%x.1), kind=kLoop, calls=%fused_computation
  %pair_fusion = (f32[8]{0}, f32[8]{0}) fusion(%x.1), kind=kLoop, calls=%fused_pair
  %copy.4 = f32[8]{0} copy(%x.1)
  ROOT %copy.3 = f32[8]{0} copy(%tanh_fusion), metadata={op_name="jit(_decode_step)/copy"}
}
"""


def test_hlo_scopes_and_scope_names():
    got = PH.hlo_scopes(HLO)
    assert got["tanh_fusion"] == \
        "jit(_decode_step)/while/body/moe/router/tanh"
    assert got["copy.3"] == "jit(_decode_step)/copy"
    assert got["pair_fusion"] == \
        "jit(_decode_step)/while/body/kv_write/neg"
    assert got["copy.4"] == ""
    assert PH.scope_of(got["pair_fusion"]) == "kv"
    assert PH.scope_of(got["copy.4"]) == "unscoped"
    assert PH.scope_of(got["tanh_fusion"]) == "moe/router"
    assert PH.scope_of(got["copy.3"]) == "unscoped"
    assert PH.scope_of(_path("kv_write", "x")) == "kv"
    assert PH.scope_of(_path("head", "dot_general")) == "head"
    assert PH.scope_of(None) == "unmapped"


# ------------------------------------------- queue and prefill readers

def _run(stamps):
    """A run whose window [10, 20) holds requests due at `due`, with the
    engine's stamps (start, admit); None for a request the client has not
    seen, "bare" for a program that stamps no start."""
    run = SV.Run(sizes={}, engine={}, peaks={}, seconds=10.0, t_proc=0.0,
                 t_open=10.0, t_close=20.0)
    for due, st in stamps:
        if st is None:
            req = None
        elif st == "bare":
            req = SimpleNamespace(admit_time=due + 1.0)
        else:
            req = SimpleNamespace(start_time=st[0], admit_time=st[1])
        run.recs.append(SV.Rec(planned=None, due=due, req=req))
    return run


def test_queue_and_prefill_medians():
    run = _run([(10.0, (10.5, 11.0)), (11.0, (12.0, 14.0)),
                (12.0, (13.0, 0.0)),       # started, not admitted by close
                (18.0, None),              # never seen: queued at the close
                (9.0, (9.5, 10.5))])       # due before the window
    q = reader("queue_wait_p50_ms")(run)
    p = reader("prefill_p50_ms")(run)
    # queue: 0.5, 1.0, 1.0, 2.0 -> 1.0 s; prefill: 0.5, 2.0, 7.0, 0 -> 1.25 s
    assert q == pytest.approx(1000.0)
    assert p == pytest.approx(1250.0)


def test_queue_and_prefill_read_nothing_without_start_stamps():
    run = _run([(10.0, "bare"), (11.0, None)])
    assert reader("queue_wait_p50_ms")(run) is None
    assert reader("prefill_p50_ms")(run) is None


# ------------------------------------- the existing readers, unchanged

@pytest.fixture(scope="module")
def existing():
    with open(os.path.join(HERE, "data", "trace_excerpt.json")) as f:
        return json.load(f)


def test_existing_readers_read_the_existing_excerpt_as_before(existing):
    """The numbers `bench/profile.py` gave on its recorded excerpt before
    the program had spans, through `Trace` and through `ProgramTrace`."""
    assert HOST_SPANS == ("bench.window", "step", "submit", "observe",
                          "wait")
    for tr in (Trace.from_dict(existing),
               PH.ProgramTrace.from_dict(existing)):
        assert tr.busy_s() == 0.093608652
        assert tr.window_s() == 0.097608958
        assert tr.top_ops(4) == [
            ["copy", 0.029677534], ["dynamic-slice_bitcast_fusion",
                                    0.018384468],
            ["fusion", 0.011396315], ["_gmm_scaled", 0.009012427]]
        assert tr.idle_gaps() == [["observe", 0.002000282],
                                  ["other", 0.002000024]]
        assert tr.op_seconds(r"gmm", "_decode_step") == 0.017757722
        assert tr.op_seconds(r"decode_kernel|paged_attn_decode",
                             "_decode_step") == 0.008415642
        assert len(tr.module_events("_decode_step")) == 1


# ------------------------------------------------ a traced smoke run

def test_traced_smoke_run_reads_the_engine_phases():
    """bench/trace_cell.py on a smoke cell on the CPU: no TPU plane, so the
    device readers find nothing, but the engine's spans and counters read,
    and the span counters agree with `last_tick`."""
    from bench import trace_cell as TC
    cell = smoke_of(load_cell("granite.chat"))
    out, _ = TC.trace_run(cell, 2 ** 40 + 11, 2.0, peaks=PEAKS, chip=False)
    ph = out["phases"]
    assert ph["traced_ticks"] > 0 and ph["tick_host_ms"] > 0
    assert ph["attn_dead_steps"] == pytest.approx(
        ph["attn_dead_steps_from_last_tick"])
    assert 0 < ph["attn_dead_steps"] < 100
    # no TPU plane: the device idles through every tick
    idle = ph["tick_idle_ms"]
    assert idle["median"] > idle["wait_median"] > 0
    names = {k for k, _ in ph["idle_phases"]}
    assert any(n.startswith("engine.") for n in names)
    assert {"queue_wait_p50_ms", "prefill_p50_ms"} <= set(out["metrics"])
    assert out["metrics"]["attn_dead_steps.decode"] == pytest.approx(
        ph["attn_dead_steps"])
    assert out["tracing"]["span_us_off"] < 50


# --------------------------------------------- the recorded chip excerpt

@pytest.fixture(scope="module")
def recorded():
    """One tick of deepseek.chat as traced on a TPU v5e
    (`bench/trace_cell.py --excerpt`): a chunk of a long prompt, then the
    decode program, inside the tick's `engine.step` span."""
    with open(os.path.join(HERE, "data", "engine_trace_excerpt.json")) as f:
        return PH.ProgramTrace.from_dict(json.load(f))


def test_recorded_tick_spans_and_counters(recorded):
    (_, s, d, args), = recorded.window_spans("engine.step")
    waits = sum(e[2] for e in recorded.spans if e[0].endswith(".wait"))
    assert PH.tick_host_ms(recorded) == pytest.approx((d - waits) / 1e6)
    assert args["decode_rows"] > 0 and args["chunk_runs"] == 1
    assert PH.attn_dead_steps(recorded) == pytest.approx(
        100 * (1 - args["live_pages"] / args["grid_pages"]))
    names = {e[0] for e in recorded.spans}
    assert names >= {"engine.step", "engine.decode.dispatch",
                     "engine.decode.wait", "engine.commit"}
    # the chunk runs in `engine.chunk`, or in `engine.admit` where its job
    # starts on this tick
    assert names & {"engine.chunk", "engine.admit"}
    from repro.serving.engine import TickRecord
    assert set(args) == set(TickRecord().counters())


def test_recorded_scopes_partition_the_decode_program(recorded):
    got = PH.decode_scope_ms(recorded)
    assert "unmapped" not in got
    assert {"kv", "attn", "moe", "unscoped", "moe/router", "moe/dispatch",
            "moe/experts", "moe/shared", "moe/combine"} <= set(got)
    (_, _, run_ns), = recorded.module_events(PH.PROGRAM)
    four = got["kv"] + got["attn"] + got["moe"] + got["unscoped"]
    assert four == pytest.approx(run_ns / 1e6, rel=0.05)
    assert sum(v for k, v in got.items() if "/" not in k) <= run_ns / 1e6
    kernels = {PH.scope_of(recorded.scopes[n]) for n, *_ in
               recorded.program_ops() if n.startswith("_")}
    assert kernels == {"attn", "moe/experts"}


def test_recorded_idle_goes_to_engine_phases(recorded):
    got = dict(PH.idle_phases(recorded, n=50))
    assert got and all(k.startswith("engine.") for k in got)
    assert sum(got.values()) == pytest.approx(
        recorded.window_s() - recorded.busy_s())
