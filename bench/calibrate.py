"""Readings that a cell's correctness limit is set from, on the chip.

    python3 bench/calibrate.py --workload granite.chat --seeds 5,6,7 \
        --seconds 40

One process runs the cell once per seed, as `bench/run.py` does (weights
from the seed, the timed window at the cell's own load, the reference over
a sample of the finished requests), and reads besides, on the same sample,
the control: the reference computed in float8_e4m3fn, the precision below
the configuration's bfloat16, judged by the tokens it puts first, put in
the program's place and run through the same comparison. Per seed it
prints one JSON line with the program's gaps and the control's, and
whether each was correct under the limits as they stand (`correct`,
`control_correct`). The limit goes above the program's largest reading and
below the control's smallest, so that `correct` is true and
`control_correct` false on every seed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.run import run_cell  # noqa: E402
from bench.spec import BENCH, load_cell, load_json  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"calibrate: no TPU (JAX found {dev.platform})",
              file=sys.stderr)
        return 2
    peaks = load_json(os.path.join(BENCH, "peaks.json"))["devices"][
        dev.device_kind]
    cell = load_cell(args.workload)
    if len(jax.devices()) < cell.chips:
        print(f"calibrate: {cell.name} needs {cell.chips} chips",
              file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run_cell(cell, seed, args.seconds, False, peaks=peaks,
                       t_proc=time.monotonic(), control=True)
        ctl = out["control"]
        print(json.dumps({
            "workload": cell.name, "seed": seed, "correct": out["correct"],
            "control_correct": ctl["correct"], "program": ctl["program"],
            "control": ctl["control"],
            "memory_peak_bytes": out["device"]["memory_peak_bytes"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
