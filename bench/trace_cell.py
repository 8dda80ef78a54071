"""Run one benchmark cell with a traced window and reduce the trace through
the program's own spans and scopes (bench/phases.py).

    python3 bench/trace_cell.py --workload <name> --seed <n> --seconds <s> \
        [--out <file.json>] [--excerpt <file.json>]

The run is `bench/run.py`'s with `--trace 1` (the same checks of the
program's configuration, weights, engine, warm-up, open-loop traffic and
traced seconds), without the correctness check. It stands beside run.py
until run.py builds `phases.ProgramTrace` itself and prints these readers;
then it goes. It prints, and writes to `--out`, one JSON object:

  metrics      the cell's end-to-end and per-layer metrics, by their readers
  phases       tick_host_ms, attn_dead_steps (also from the engine's
               `last_tick` of every traced tick, read without the
               profiler), decode_scope_ms (device ms per decode run by
               model scope; decode_step_ms is among the metrics),
               idle_phases, the device's idle inside a tick and inside its
               decode wait (tick_idle_ms), and the decode program's
               costliest operations with their scopes
  tracing      the cost of tracing: host ms per tick and the median gap
               between tokens, inside the traced part of the window and
               outside it; and a span's cost with no profiler running
  breakdown    device_ops and idle_gaps, as bench/run.py prints them

`--excerpt` also writes one traced tick that runs both the decode and the
chunk program, clipped from the trace with its spans and scopes, as the
tests' recorded trace (`ProgramTrace.from_dict`).
"""
from __future__ import annotations

import time

T_PROC = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import phases as PH  # noqa: E402
from bench import serve as SV  # noqa: E402
from bench import traffic as TR  # noqa: E402
from bench.run import (TRACE_SECONDS, build, checkout_cache,  # noqa: E402
                       log, reference)
from bench.spec import BENCH, load_cell, load_json, reader  # noqa: E402


class TickDriver(SV.Driver):
    """The harness's Driver, keeping the engine's `last_tick` after each
    step beside the step's host span."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.ticks: list = []

    def observe(self, done, st):
        self.ticks.append((st.t0, st.t1, self.eng.last_tick))
        return super().observe(done, st)


def span_cost_us(n: int = 200_000) -> float:
    """Host microseconds of one `span` with no profiler running."""
    from repro.runtime.trace import span
    t0 = time.perf_counter()
    for _ in range(n):
        with span("engine.step"):
            pass
    return (time.perf_counter() - t0) / n * 1e6


def median_ms(xs: list) -> float | None:
    return statistics.median(xs) * 1e3 if xs else None


def tick_idle_ms(tr: PH.ProgramTrace) -> dict:
    """Device idle inside each of the window's `engine.step` spans, and the
    part of it inside the tick's `engine.decode.wait`: median and mean over
    the ticks, in ms. A mean well above the median marks idle gathered in
    a few ticks, which a median gap between tokens does not see."""
    busy = tr.busy_intervals()

    def idle(a, b):
        return b - a - sum(min(b, y) - max(a, x) for x, y in busy
                           if x < b and y > a)
    waits = tr.window_spans("engine.decode.wait")
    ticks, in_wait = [], []
    for _, s, d, _ in tr.window_spans("engine.step"):
        ticks.append(idle(s, s + d))
        in_wait.append(sum(idle(ws, ws + wd) for _, ws, wd, _ in waits
                           if s <= ws and ws + wd <= s + d))
    if not ticks:
        return {}
    return {"median": statistics.median(ticks) / 1e6,
            "mean": statistics.fmean(ticks) / 1e6,
            "wait_median": statistics.median(in_wait) / 1e6,
            "wait_mean": statistics.fmean(in_wait) / 1e6}


def excerpt(tr: PH.ProgramTrace) -> dict | None:
    """The first traced tick that runs the decode program and a chunk (or
    the first that runs the decode program), clipped from the trace: the
    window becomes that tick's `engine.step` span, and every list keeps
    what overlaps it."""
    ticks = [e for e in tr.window_spans("engine.step")
             if e[3].get("decode_rows", 0)]
    if not ticks:
        return None
    _, lo, d, _ = next((e for e in ticks if e[3].get("chunk_runs", 0)),
                       ticks[0])
    hi = lo + d

    def keep(events):
        return [list(e) for e in events if e[1] < hi and e[1] + e[2] > lo]
    ops = keep(tr.ops)
    names = {e[0] for e in ops}
    host = [e for e in keep(tr.host) if e[0] != PH.WINDOW]
    return {"ops": ops, "modules": keep(tr.modules),
            "host": host + [[PH.WINDOW, lo, hi - lo]],
            "spans": keep(tr.spans),
            "scopes": {k: v for k, v in tr.scopes.items() if k in names}}


def trace_run(cell, seed: int, seconds: float, *, peaks: dict,
              chip: bool = True) -> tuple[dict, dict | None]:
    """One traced run of `cell`: (the result object, the excerpt).
    `chip=False` skips the look for the chip's kernels (the CPU tests)."""
    import jax
    if chip:
        log(f"compile cache: {checkout_cache()}")
    clock = SV.CompileClock()
    conf, traffic = cell.config, cell.traffic
    ref = reference(conf)
    sz = ref.sizes(conf)
    pcfg = SV.program_config(conf)
    SV.check_program_matches(pcfg, sz)
    _, eng = build(cell, seed, ref, sz, pcfg)
    if chip:
        log(f"chip paths: {SV.check_chip_paths(eng)}")
    plan = TR.plan(traffic, seed, seconds, sz["vocab"])
    SV.warm_up(eng, plan, sz["vocab"])
    gc.collect()
    gc.freeze()
    run = SV.Run(sizes=sz, engine=traffic["engine"], peaks=peaks,
                 seconds=seconds, t_proc=T_PROC, chips=cell.chips,
                 compiles=clock)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    drv = TickDriver(eng, plan, run, trace_dir=trace_dir,
                     trace_s=min(TRACE_SECONDS, seconds))
    drv.loop()
    gc.unfreeze()
    cost_us = span_cost_us()
    tr = PH.ProgramTrace.from_xplane(trace_dir, SV.decode_hlo(eng))
    shutil.rmtree(trace_dir, ignore_errors=True)
    run.trace = tr

    metrics = {}
    for m in cell.end_to_end + cell.per_layer:
        val = reader(m["name"], cell.root)(run)
        if val is not None:
            metrics[m["name"]] = float(val)

    a, b = run.trace_span
    inside = [t for t in drv.ticks if a <= t[0] and t[1] <= b]
    outside = [t for t in drv.ticks if run.t_open <= t[0] and t[1] < a]
    gaps_in, gaps_out = [], []
    for r in run.recs:
        for x, y in zip(r.times, r.times[1:]):
            if y > x and a <= y <= b:
                gaps_in.append(y - x)
            elif y > x and run.t_open <= y < a:
                gaps_out.append(y - x)
    counted = [t[2] for t in inside if t[2].grid_pages]
    live = sum(t.live_pages for t in counted)
    grid = sum(t.grid_pages for t in counted)
    lo, hi = tr.window()
    steps = len(tr.window_spans("engine.step"))
    n_spans = sum(lo <= e[1] and e[1] + e[2] <= hi for e in tr.spans)
    scopes = PH.decode_scope_ms(tr)
    runs = max(1, len(tr.module_events(PH.PROGRAM)))
    own: dict = {}
    for name, _, _, t in tr.program_ops():
        own[name] = own.get(name, 0) + t
    top = sorted(own.items(), key=lambda kv: -kv[1])
    dev = jax.devices()[0]
    out = {
        "workload": cell.name, "seed": seed,
        "device": {"kind": dev.device_kind,
                   "busy_s": tr.busy_s(), "window_s": tr.window_s()},
        "metrics": metrics,
        "phases": {
            "tick_host_ms": PH.tick_host_ms(tr),
            "attn_dead_steps": PH.attn_dead_steps(tr),
            "attn_dead_steps_from_last_tick":
                100.0 * (1 - live / grid) if grid else None,
            "decode_scope_ms": scopes,
            "decode_scope_sum_ms": sum(
                v for k, v in (scopes or {}).items() if "/" not in k),
            "decode_runs": len(tr.module_events(PH.PROGRAM)),
            "scoped_ops": len(tr.scopes),
            "idle_phases": PH.idle_phases(tr),
            "tick_idle_ms": tick_idle_ms(tr),
            "traced_ticks": steps,
            "top_decode_ops": [
                [n, t / runs / 1e6, PH.scope_of(tr.scopes.get(n)),
                 (tr.scopes.get(n) or "")[-100:]] for n, t in top[:15]],
            "unmapped_ops": [[n, t / runs / 1e6] for n, t in top
                             if n not in tr.scopes][:10],
        },
        "tracing": {
            "step_ms_traced": median_ms([t[1] - t[0] for t in inside]),
            "step_ms_untraced": median_ms([t[1] - t[0] for t in outside]),
            "itl_p50_ms_traced": median_ms(gaps_in),
            "itl_p50_ms_untraced": median_ms(gaps_out),
            "span_us_off": cost_us,
            "spans_per_tick": n_spans / steps if steps else None,
        },
        "breakdown": {"device_ops": tr.top_ops(),
                      "idle_gaps": tr.idle_gaps()},
    }
    return out, excerpt(tr)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    ap.add_argument("--excerpt")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)

    import jax
    dev = jax.devices()[0]
    peaks = load_json(os.path.join(BENCH, "peaks.json"))["devices"].get(
        dev.device_kind)
    if dev.platform != "tpu" or peaks is None:
        log(f"trace_cell: no TPU with known peaks ({dev.device_kind})")
        return 2
    if len(jax.devices()) < cell.chips:
        log(f"trace_cell: {cell.name} needs {cell.chips} chips")
        return 2
    out, exc = trace_run(cell, args.seed, args.seconds, peaks=peaks)
    text = json.dumps(out)
    print(text, flush=True)
    for path, body in ((args.out, text),
                       (args.excerpt, json.dumps(exc) if exc else None)):
        if path and body is not None:
            os.makedirs(os.path.dirname(os.path.abspath(path)),
                        exist_ok=True)
            with open(path, "w") as f:
                f.write(body)
    return 0


if __name__ == "__main__":
    sys.exit(main())
