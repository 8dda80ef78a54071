"""The engine's own spans, tick counters and request stamps, and the model
scopes in the decode program's metadata.

The counters are checked against what the benchmark's harness
(`bench/serve.py`) infers from outside the engine on its smoke cell; the
spans against a profiler trace recorded on the CPU; the scopes against the
lowered decode step, which must be the same program with and without them.
"""
import dataclasses
import glob
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

from repro.configs.registry import get_config  # noqa: E402
from repro.kernels.paged_attn import decode_tick_pages  # noqa: E402
from repro.models.model import model_init  # noqa: E402
from repro.runtime.fault import STEP_WINDOW, StepSupervisor  # noqa: E402
from repro.serving import ServingEngine  # noqa: E402
from repro.serving import engine as ENG  # noqa: E402

ARCH = "granite-moe-3b-a800m"


def _engine(arch=ARCH, **kw):
    cfg = get_config(arch, smoke=True)
    params = model_init(jax.random.PRNGKey(0), cfg)
    opts = dict(num_slots=4, max_tokens=64, paged=True, page_size=8,
                prefill_chunk=16)
    opts.update(kw)
    return ServingEngine(params, cfg, **opts)


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(0, 200, size=n,
                                                dtype=np.int32)


# ------------------------------------------------------------- counters

def test_last_tick_counts_what_the_tick_ran():
    eng = _engine()
    assert eng.last_tick is None
    eng.submit(_prompt(10, 0), 4)            # one-shot prefill
    eng.submit(_prompt(30, 1), 4)            # two chunks of 16
    eng.step()
    t = eng.last_tick
    # tick 1: the long prompt starts its chunk job, the short one prefills
    # one-shot, and the short one's row decodes once
    assert t.oneshot == [10] and t.chunks == [(0, 16)]
    assert t.decode_rows == 1
    assert t.live_pages == 10 // 8 + 1
    assert t.grid_pages == 4 * (64 // 8)
    assert t.step == eng.step_count
    eng.step()
    t = eng.last_tick
    # tick 2: the chunk job's last chunk (14 real tokens) installs it, so
    # both rows decode
    assert t.oneshot == [] and t.chunks == [(16, 14)]
    assert t.decode_rows == 2
    assert t.live_pages == (11 // 8 + 1) + (30 // 8 + 1)
    c = t.counters()
    assert c["chunk_valid"] == 14
    assert c["chunk_runs"] == 1 and c["oneshot_tokens"] == 0
    assert all(isinstance(v, int) for v in c.values())


def _harness_run(seconds=2.0, seed=2 ** 40 + 7):
    """The benchmark's first cell at its smoke size, driven by the
    harness's own Driver, with `last_tick` taken after every step."""
    from bench import serve as SV
    from bench import traffic as TR
    from bench.run import reference
    from bench.spec import load_cell, load_json
    from bench.tests.smoke import PEAKS, smoke_of

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = smoke_of(load_cell(bench["workloads"][0]["name"]))
    conf, traffic = cell.config, cell.traffic
    ref = reference(conf)
    sz = ref.sizes(conf)
    weights = ref.make_weights(sz, seed)
    eng = SV.make_engine(weights, SV.program_config(conf), traffic["engine"])
    plan = TR.plan(traffic, seed, seconds, sz["vocab"])
    SV.warm_up(eng, plan, sz["vocab"])
    run = SV.Run(sizes=sz, engine=traffic["engine"], peaks=PEAKS,
                 seconds=seconds, t_proc=time.monotonic())
    ticks = []

    class Driver(SV.Driver):
        def observe(self, done, st):
            ticks.append(self.eng.last_tick)
            return super().observe(done, st)

    Driver(eng, plan, run).loop()
    return eng, run, ticks


@pytest.fixture(scope="module")
def harness_run():
    return _harness_run()


def test_last_tick_equals_the_harness_inference(harness_run):
    """Decode rows, chunk runs and one-shot prompts of every tick, as the
    engine counts them and as the harness infers them from the tokens it
    sees; the live pages on the harness's positions."""
    eng, run, ticks = harness_run
    assert len(ticks) == len(run.steps) > 0
    ps, slots = eng.pool.page_size, eng.pool.num_slots
    width = eng.pool.block_table.shape[1]
    kinds = set()
    for st, tick in zip(run.steps, ticks):
        assert tick.decode_rows == len(st.decode)
        assert sorted(tick.oneshot) == sorted(st.oneshot)
        assert sorted(tick.chunks) == sorted((s, v) for s, v, _ in st.chunks)
        live, _ = decode_tick_pages(st.decode, [True] * len(st.decode),
                                       ps, len(st.decode), width)
        assert tick.live_pages == live
        assert tick.grid_pages == (slots * width if st.decode else 0)
        kinds.update(k for k in ("decode", "oneshot", "chunks")
                     if getattr(st, k))
    assert kinds == {"decode", "oneshot", "chunks"}


def test_dead_steps_metric_equals_the_tick_counters(harness_run):
    """`attn_dead_steps.decode`, read from the harness's positions over a
    trace that spans the run, equals 1 - sum(live) / sum(grid) of the
    engine's own counters."""
    from bench.spec import reader
    eng, run, ticks = harness_run
    traced = dataclasses.replace(
        run, trace=object(), trace_span=(run.steps[0].t0, run.steps[-1].t1))
    counted = [t for t in ticks if t.grid_pages]
    want = 100 * (1 - sum(t.live_pages for t in counted)
                  / sum(t.grid_pages for t in counted))
    got = reader("attn_dead_steps.decode")(traced)
    assert got == pytest.approx(want) and 0 < got < 100
    assert reader("attn_dead_steps.decode")(run) is None    # untraced


def test_queue_and_prefill_stamps_add_up_to_the_first_token(harness_run):
    """Per request, (start - due) + (admit - start) lies within the tick
    that emitted the first token: no later than the harness's stamp of it,
    and no more than that tick earlier."""
    _, run, _ = harness_run
    tick_of = {s.t1: s for s in run.steps}
    seen = 0
    for rec in run.recs:
        if not rec.times:
            continue
        req = rec.req
        assert req.arrival_time <= req.start_time <= req.admit_time
        st = tick_of[rec.times[0]]
        waited = (req.start_time - rec.due) + (req.admit_time - req.start_time)
        ttft = rec.times[0] - rec.due
        assert ttft - (st.t1 - st.t0) <= waited <= ttft
        seen += 1
    assert seen > 0


@pytest.mark.parametrize("path", ["oneshot", "chunked", "prefix_hit"])
def test_start_time_lies_between_arrival_and_first_token(path):
    eng = _engine(prefix_share=True)
    prompt = _prompt(40 if path == "chunked" else 12, 3)
    eng.submit(prompt, 3)
    while eng.has_work():
        eng.step()
    if path == "prefix_hit":
        eng.submit(prompt, 3)          # the same prompt: a full-prompt hit
        while eng.has_work():
            eng.step()
    s = eng.stats()
    assert s["statuses"] == {"DONE": len(eng.finished)}
    assert (s["chunk_ticks"] > 0) == (path == "chunked")
    assert (s["prefix_hits"] > 0) == (path == "prefix_hit")
    for req in eng.finished.values():
        assert 0 < req.arrival_time <= req.start_time <= req.admit_time \
            <= req.finish_time


# ---------------------------------------------------------------- spans

def test_engine_spans_nest_on_the_profiler_clock(tmp_path):
    from jax.profiler import ProfileData

    eng = _engine()
    eng.submit(_prompt(10, 0), 3)
    eng.submit(_prompt(30, 1), 3)
    eng.step()                               # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    while eng.has_work():
        eng.step()
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
              for p in ProfileData.from_file(path).planes
              if p.name.startswith("/host:") for line in p.lines
              for e in line.events if e.name.startswith("engine.")]
    names = {e[0] for e in events}
    assert {"engine.step", "engine.expire", "engine.chunk", "engine.admit",
            "engine.decode.dispatch", "engine.decode.wait",
            "engine.commit", "engine.prefill.wait"} <= names
    steps = [e for e in events if e[0] == "engine.step"]
    assert len(steps) == eng.step_count - 1

    def parent(e, name):
        return [p for p in events if p[0] == name and p[1] <= e[1]
                and e[2] <= p[2]]
    for e in events:
        if e[0] != "engine.step":
            assert len(parent(e, "engine.step")) == 1, e
        if e[0] == "engine.decode.wait":
            assert parent(e, "engine.decode.dispatch"), e
        if e[0] == "engine.prefill.wait":
            assert parent(e, "engine.chunk") or parent(e, "engine.admit"), e
    args = steps[0][3]
    assert set(args) == set(ENG.TickRecord().counters())
    # every token but the prefills' first came from a decode row; the
    # first tick, outside the trace, decoded the short prompt's row once
    assert sum(s[3]["decode_rows"] for s in steps) == sum(
        len(r.tokens) - 1 for r in eng.finished.values()) - 1


def test_step_times_are_a_bounded_window():
    sup = StepSupervisor()
    for i in range(STEP_WINDOW + 10):
        sup.run(lambda: 0, step=i)
    assert len(sup.stats.times) == STEP_WINDOW
    assert "tick_ms_median" not in _engine(paged=False,
                                           prefill_chunk=0).stats()


# --------------------------------------------------------------- scopes

SCOPES = ("kv_read", "attn", "moe", "kv_write", "embed", "head")


def _lower_decode(eng):
    pool = eng.pool
    return ENG._decode_step.lower(
        eng.params, pool.state, jnp.asarray(pool.pending),
        jnp.asarray(pool.active_mask()), eng.cfg)


@pytest.mark.parametrize("arch", [ARCH, "deepseek-moe-16b"])
def test_decode_program_carries_the_model_scopes(arch, monkeypatch):
    cfg = get_config(arch, smoke=True)
    cfg = cfg.with_overrides(paged_attn="kernel", moe=dataclasses.replace(
        cfg.moe, backend="pallas"))
    eng = ServingEngine(model_init(jax.random.PRNGKey(0), cfg), cfg,
                        num_slots=4, max_tokens=64, paged=True, page_size=8)
    low = _lower_decode(eng)
    paths = set(re.findall(r'op_name="([^"]*)"', low.compile().as_text()))
    for scope in SCOPES + ("router", "dispatch", "experts", "combine"):
        assert any(f"/{scope}/" in p for p in paths), scope
    if arch == "deepseek-moe-16b":
        assert any("/moe/shared/" in p for p in paths)
    # the scopes live in the metadata only: without them the program is
    # the same text
    scoped = low.as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: __import__("contextlib").nullcontext())
    jax.clear_caches()
    plain = _lower_decode(eng)
    assert plain.as_text() == scoped

    def located(text):
        return {s for s in SCOPES
                if re.search(rf'loc\("([^"/]*/)*{s}/', text)}
    assert located(low.as_text(debug_info=True)) == set(SCOPES)
    assert located(plain.as_text(debug_info=True)) == set()
