"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is the `BENCHMARK.json` workload of that name: its configuration
file, its traffic file (`bench/traffic/<traffic>.json`) and the metrics
that list it. The run creates the weights from the seed on the device,
builds the program's `ServingEngine` over them (paged pool, chunked
prefill, prompt buckets, the chip's kernels), warms every shape the
traffic uses, offers the traffic on the wall clock for `--seconds`, and
prints as its last line one JSON object: `correct`, `attempted`, `failed`,
`metrics` (the end-to-end metrics, or with `--trace 1` the per-layer ones,
read from a profiler trace of the window's last seconds), `device`, with
`--trace 1` a `breakdown`, and last `compared`: each number the
correctness check compared, with its limit (also the last lines of
standard error). It exits non-zero, printing no result, when JAX finds no
TPU or fewer chips than the cell asks for, or the device is not in
`bench/peaks.json`.

A cell on more than one chip runs over the first `chips` devices: the
weights made already laid out by the program's serve-mode rules, the
engine over the program's serving mesh, and the reference's expert sum
split alike. Memory is the fullest chip's peak.
"""
from __future__ import annotations

import time

T_PROC = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import check as CHK  # noqa: E402
from bench import serve as SV  # noqa: E402
from bench import traffic as TR  # noqa: E402
from bench.spec import BENCH, load_cell, load_json, reader  # noqa: E402

TRACE_SECONDS = 6.0     # the traced part of a --trace 1 window


def reference(conf: dict):
    return importlib.import_module(f"bench.references.{conf['reference']}")


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def checkout_cache() -> str:
    """Keep JAX's persistent compile cache at a fixed path inside the
    checkout, whatever the environment names, and cache every program: a
    run shares compiled programs with the runs of its own checkout and with
    nothing else. Returns the directory."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return enable_compile_cache()


def build(cell, seed: int, ref, sz: dict, pcfg) -> tuple:
    """The weights from the seed and the program's engine over them, on the
    cell's chips: (weights, engine)."""
    import jax
    if cell.chips == 1:
        weights = jax.block_until_ready(ref.make_weights(sz, seed))
        return weights, SV.make_engine(weights, pcfg, cell.traffic["engine"])
    # over the cell's chips: the engine first, since the program makes its
    # page pool whole on the first chip before laying it out over the mesh,
    # and a chip that already holds its share of the weights has no room
    # for that; then the weights, made already laid out by the program's
    # serve-mode rules
    mesh = SV.make_mesh(cell.chips)
    eng = SV.make_engine(None, pcfg, cell.traffic["engine"], mesh=mesh)
    shapes = jax.eval_shape(lambda: ref.make_weights(sz, seed))
    eng.params = jax.block_until_ready(ref.make_weights(
        sz, seed, SV.weight_shardings(shapes, pcfg, mesh)))
    return eng.params, eng


def run_cell(cell, seed: int, seconds: float, trace: bool, *, peaks: dict,
             t_proc: float, chip: bool = True, control: bool = False,
             keep: list | None = None) -> dict:
    """One run of `cell`; returns the result line's object. `chip=False`
    skips the look for the chip's kernels (the CPU tests' smoke cells);
    `control` also puts the control in the program's place on the same
    sample and runs it through the same comparison (the calibration of the
    limits, bench/calibrate.py): its numbers, its `compared` and its
    `correct` go under `control`. `keep`, a list, receives the run's
    record (the sweep reads it)."""
    import jax

    if chip:
        log(f"compile cache: {checkout_cache()}")
    clock = SV.CompileClock()
    conf, traffic = cell.config, cell.traffic
    ref = reference(conf)
    sz = ref.sizes(conf)
    pcfg = SV.program_config(conf)
    SV.check_program_matches(pcfg, sz)
    weights, eng = build(cell, seed, ref, sz, pcfg)
    if chip:
        log(f"chip paths: {SV.check_chip_paths(eng)}")
    plan = TR.plan(traffic, seed, seconds, sz["vocab"])
    log(f"warm-up requests: {SV.warm_up(eng, plan, sz['vocab'])}")
    # what tracing and compiling left behind is collected now, in set-up,
    # and not by a collector pause inside the window
    gc.collect()
    gc.freeze()

    run = SV.Run(sizes=sz, engine=traffic["engine"], peaks=peaks,
                 seconds=seconds, t_proc=t_proc, chips=cell.chips,
                 compiles=clock)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    drv = SV.Driver(eng, plan, run, trace_dir=trace_dir,
                    trace_s=min(TRACE_SECONDS, seconds) if trace else 0.0)
    drv.loop()
    if keep is not None:
        keep.append(run)
    stats = eng.stats()
    devs = jax.devices()[:cell.chips]
    dev = devs[0]
    # the fullest chip's peak
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    if trace:
        from bench.profile import Trace, kernel_names
        run.trace = Trace.from_xplane(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if cell.chips > 1:
            # under a mesh each kernel's operation is named after the
            # shard_map it runs in: name it from the compiled tick
            run.trace.name_kernels(kernel_names(SV.decode_hlo(eng)))
        share = [b / run.trace.window_s() for b in run.trace.chip_busy_s()]
        print("device busy share of the traced window by chip: "
              + ", ".join(f"{x:.4f}" for x in share))

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        val = reader(m["name"], cell.root)(run)
        if val is not None:
            metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
    win = run.window_recs()
    print("traffic: prompts " + TR.describe([r.prompt_len for r in win])
          + "; outputs " + TR.describe([r.planned.max_new for r in win]))
    print("generator lateness ms: " + TR.describe(
        [1e3 * x for x in run.lateness]))
    kinds = [e[1] for e in clock.between(0, run.t_open)]
    print(f"compile cache before the window: {kinds.count('hit')} hits, "
          f"{kinds.count('miss')} misses")
    late = [e[3] for e in clock.between(run.t_open, run.t_close)
            if e[1] == "compile"]
    if late:
        log(f"compiled inside the window: {late}")
    print(f"engine: ticks {len(run.steps)}, statuses {stats['statuses']}, "
          f"chunk ticks {stats['chunk_ticks']}, peak active "
          f"{stats['peak_active']}")

    # the reference runs once the engine's state is freed
    del drv, eng
    gc.unfreeze()
    gc.collect()
    lim = cell.config["limits"]
    chosen = CHK.sample(run, seed)
    t_ref = time.monotonic()
    got = (CHK.gap_numbers(CHK.gaps(chosen, weights, sz, ref)) if chosen
           else dict.fromkeys(CHK.NUMBERS))
    log(f"reference: {len(chosen)} requests "
        f"({sum(r.req.status != 'DONE' for r in chosen)} in flight), "
        f"{sum(len(r.req.tokens) for r in chosen)} served tokens, "
        f"{time.monotonic() - t_ref:.1f} s")
    log("gaps: " + ", ".join(f"{k} {v!r}" for k, v in got.items()))
    counts = CHK.faults(run, stats["tick_retries"])
    compared = CHK.compare(got, lim, counts)
    correct = CHK.passes(compared)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    out = {"correct": bool(correct), "attempted": len(win),
           "failed": sum(CHK.failed(r) for r in win),
           "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s()
        out["breakdown"] = {"device_ops": run.trace.top_ops(),
                            "idle_gaps": run.trace.idle_gaps()}
    if control:
        # the control in the program's place, through the same comparison
        ctl = (CHK.gap_numbers(CHK.gaps(chosen, weights, sz, ref,
                                        control=True))
               if chosen else dict.fromkeys(CHK.NUMBERS))
        ctl_compared = CHK.compare(ctl, lim, counts)
        out["control"] = {"program": got, "control": ctl,
                          "correct": CHK.passes(ctl_compared),
                          "compared": {k: {"value": v, "limit": lv}
                                       for k, (v, lv) in ctl_compared.items()}}
    out["compared"] = {k: {"value": v, "limit": lv}
                       for k, (v, lv) in compared.items()}
    for k, (v, lv) in compared.items():
        log(f"compared {k}: {v!r} limit {lv!r}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"bench: no TPU (JAX found {devs[0].platform})")
        return 2
    if len(devs) < cell.chips:
        log(f"bench: {args.workload} needs {cell.chips} chips, found "
            f"{len(devs)}")
        return 2
    table = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if devs[0].device_kind not in table:
        log(f"bench: no peaks for device kind {devs[0].device_kind!r}")
        return 2
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   peaks=table[devs[0].device_kind], t_proc=T_PROC)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
