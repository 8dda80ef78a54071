"""Benchmark harness — one entry per paper artifact + the roofline table.

``PYTHONPATH=src python -m benchmarks.run`` prints, per benchmark, a
``name,us_per_call,derived`` CSV row (wall time of the benchmark itself plus
its headline derived metric), then the detailed tables.
"""
from __future__ import annotations

import os
import sys
import time


def _timed(name, fn):
    t0 = time.perf_counter()
    out = fn()
    us = (time.perf_counter() - t0) * 1e6
    return name, us, out


def main() -> None:
    # Force multi-device CPU BEFORE the first jax import so moe_path's
    # sharded-forward row is emitted from this entry point too — otherwise
    # this harness silently overwrites BENCH_moe_path.json with a skipped
    # row on single-device hosts.
    if "jax" not in sys.modules:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()

    from benchmarks import paper_fig4, paper_fig5, paper_table1, roofline
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    rows = []

    name, us, fig4 = _timed("paper_fig4_cache", paper_fig4.run)
    rows.append((name, us,
                 f"kvgo_lat_x8={fig4['claims']['lat_x_vs_none@8']:.2f}"))

    name, us, fig5 = _timed("paper_fig5_sched", paper_fig5.run)
    best = max(v["eff_x"] for k, v in fig5.items() if k != "baseline")
    rows.append((name, us, f"best_area_eff_x={best:.2f}"))

    name, us, t1 = _timed("paper_table1_total", paper_table1.run)
    rows.append((name, us,
                 f"s4o_density={t1['S4O+KVGO']['density']:.1f}GOPS/W/mm2"))

    def _roof():
        return roofline.load_all()
    name, us, roof = _timed("roofline_table", _roof)
    if roof:
        worst = min(r["roofline"]["mfu_upper_bound"] for r in roof)
        rows.append((name, us, f"cells={len(roof)},min_mfu_bound={worst:.3f}"))
    else:
        rows.append((name, us, "cells=0 (run repro.launch.dryrun first)"))

    # kernel micro-benchmarks (interpret mode on CPU: correctness-path timing)
    def _kern():
        import jax, jax.numpy as jnp
        from repro.kernels.ops import moe_ffn_pallas
        from repro.core.routing import token_choice
        key = jax.random.PRNGKey(0)
        T, d, f, E, k = 256, 256, 512, 8, 2
        x = jax.random.normal(key, (T, d), jnp.float32) * 0.1
        bank = {"wg": jax.random.normal(key, (E, d, f)) * 0.05,
                "wi": jax.random.normal(key, (E, d, f)) * 0.05,
                "wo": jax.random.normal(key, (E, f, d)) * 0.05}
        gate = jax.random.normal(key, (d, E)) * 0.1
        r = token_choice(x, gate, k)
        y = moe_ffn_pallas(x, r.expert_idx, r.weights, bank, E, bn=64)
        y.block_until_ready()
        t0 = time.perf_counter()
        for _ in range(3):
            moe_ffn_pallas(x, r.expert_idx, r.weights, bank, E,
                           bn=64).block_until_ready()
        return (time.perf_counter() - t0) / 3 * 1e6
    name, us, per_call = _timed("kernel_moe_gmm_interpret", _kern)
    rows.append((name, us, f"us_per_call={per_call:.0f}"))

    # MoE execution-path trajectory: xla-masked vs pallas, dense vs selected
    # decode. Writes a FRESH report (gitignored) — never the committed
    # BENCH_moe_path.json baseline that check_regression.py gates CI against
    from benchmarks import moe_path
    name, us, mp = _timed(
        "moe_path",
        lambda: moe_path.run(smoke=True, out="BENCH_moe_path.fresh.json"))
    rows.append((name, us,
                 f"fwd_flop_ratio_xla={mp['forward']['redundant_flop_ratio_xla']:.2f}"
                 f"/pallas={mp['forward']['redundant_flop_ratio_pallas']:.2f},"
                 f"decode_row_x={mp['decode']['row_ratio_dense_over_selected']:.1f}"))

    # continuous-batching throughput on a Poisson trace (refreshes the
    # committed BENCH_serve_throughput.json; the deterministic
    # paged-occupancy rows are CI-gated, the wall-clock rows archived only)
    from benchmarks import serve_throughput
    name, us, st = _timed(
        "serve_throughput",
        lambda: serve_throughput.run(smoke=True, slot_counts=(1, 4),
                                     out="BENCH_serve_throughput.json"))
    best = max(st["rows"], key=lambda r: r["tok_per_s"])
    pd = st["paged_vs_dense"]
    rows.append((name, us,
                 f"best_tok_per_s={best['tok_per_s']:.1f}@"
                 f"{best['slots']}slots,p95_ms={best['p95_ms']:.0f},"
                 f"paged_streams={pd['paged']['max_concurrent']}"
                 f"v{pd['dense']['max_concurrent']}"))

    print("name,us_per_call,derived")
    for n, u, d in rows:
        print(f"{n},{u:.0f},{d}")
    print()

    paper_fig4.main()
    print()
    paper_fig5.main()
    print()
    paper_table1.main()
    print()
    roofline.main()


if __name__ == "__main__":
    main()
