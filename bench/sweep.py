"""Find a cell's knee once, by a sweep of open-loop rates on the chip.

    python3 bench/sweep.py --workload granite.chat --seed 7 \
        --rates 0.3,0.4,0.5 --seconds 30 [--dump chiprun_out/trace-granite]

One process runs the cell once per rate, as `bench/run.py` does, with the
cell's traffic at that rate (compiled programs are shared); rate i runs on
seed + i. Each run also puts the control in the program's place, as
`bench/calibrate.py` does, so a sweep reads the correctness check of the
program and of the control on as many seeds as it has rates. Per rate it
prints one JSON line: the cell's end-to-end metrics as their readers give
them, whether the program and the control were correct, their gaps, the
requests due, done and still waiting, tokens/s, and the mean time to first
token of the window's first and last quarter of requests. The knee is
the highest rate whose queue stays flat: the last quarter's first tokens
no slower than the first quarter's. With --dump, the first rate's run is traced and one decode tick
of its trace is cut out (as `bench/tests/data/trace_excerpt.json` was).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

from bench.run import run_cell  # noqa: E402
from bench.spec import BENCH, load_cell, load_json  # noqa: E402


def dump_excerpt(trace, out_dir: str) -> None:
    """One decode tick from the middle of the traced window, with 2 ms on
    each side, as the tests' recorded trace."""
    os.makedirs(out_dir, exist_ok=True)
    ticks = [e for e in trace.modules if "_decode_step" in e[0]]
    _, s0, d0 = ticks[len(ticks) // 2]
    a, b = s0 - 2_000_000, s0 + d0 + 2_000_000

    def cut(events):
        return [e for e in events if a <= e[1] and e[1] + e[2] <= b]
    host = [e for e in cut(trace.host) if e[0] != "bench.window"]
    with open(os.path.join(out_dir, "trace_excerpt.json"), "w") as f:
        json.dump({"ops": cut(trace.ops), "modules": cut(trace.modules),
                   "host": host + [("bench.window", a, b - a)]}, f,
                  separators=(",", ":"))


def quarter_ttft_ms(run, last: bool) -> float:
    """Mean time to first token of the first or last quarter, by due time,
    of the requests due in the window."""
    win = sorted(run.window_recs(), key=lambda r: r.due)
    q = max(1, len(win) // 4)
    part = win[-q:] if last else win[:q]
    return float(np.mean([(min(r.times[0], run.t_close) if r.times
                           else run.t_close) - r.due for r in part])) * 1e3


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--dump", default="")
    args = ap.parse_args()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    peaks = load_json(os.path.join(BENCH, "peaks.json"))["devices"][
        dev.device_kind]
    cell = load_cell(args.workload)
    if len(jax.devices()) < cell.chips:
        print(f"sweep: {cell.name} needs {cell.chips} chips",
              file=sys.stderr)
        return 2
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        at = dataclasses.replace(
            cell, traffic={**cell.traffic, "rate_per_s": rate})
        kept = []
        dump = bool(args.dump) and i == 0
        out = run_cell(at, args.seed + i, args.seconds, dump, peaks=peaks,
                       t_proc=time.monotonic(), control=True, keep=kept)
        run = kept[0]
        if dump:
            dump_excerpt(run.trace, args.dump)
        win = run.window_recs()
        print(json.dumps({
            "rate": rate, "seed": args.seed + i, "correct": out["correct"],
            "control_correct": out["control"]["correct"],
            "program": out["control"]["program"],
            "control": out["control"]["control"],
            "due": len(win),
            "done": sum(len(r.times) == r.planned.max_new for r in win),
            "waiting_at_close": sum(1 for r in win if not r.times),
            "tok_s": run.tokens_in_window() / args.seconds,
            "ttft_first_quarter_ms": quarter_ttft_ms(run, last=False),
            "ttft_last_quarter_ms": quarter_ttft_ms(run, last=True),
            "ticks": len(run.steps),
            "mean_active": float(np.mean([len(s.decode) for s in run.steps
                                          if s.decode])),
            **{k: v["value"] for k, v in out["metrics"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
