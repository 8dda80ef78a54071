"""MoE execution-path benchmark: xla-masked vs Pallas tile-dispatch, and
GO-cache decode dense-vs-selected — the perf trajectory for the C1/C4 engine.

Measures, on the SAME weights and routing:

  forward   group_forward on backend="xla" (masked member loop: g x redundant
            FLOPs over the pooled group buffer) vs backend="pallas" (each
            routed pair streams through the grouped GEMM exactly once).
            Reports us/call and the redundant-FLOP ratio of each path
            (FFN rows computed / routed pairs; 1.0 = zero redundancy).
  sharded   moe_forward_ep under a ("data", "model") mesh: the per-shard
            [E_loc, C, d] capacity buffers of backend="xla" vs the per-shard
            tile plans of backend="pallas" (each shard plans only its local
            experts; drop parity pinned in tests). Skipped gracefully on
            single-device hosts — `main()` forces host devices via XLA_FLAGS
            before jax imports, so the CLI always emits the row on CPU.
  decode    the GO-cache step with the dense fallback (expert_ffn_all: B*E
            FFN rows per step) vs the selected-experts path
            (kernels/ops.py:go_selected_ffn: the static-capacity decode
            plan with the router's k as occupancy hint). Reports us/step
            (averaged over the tick sequence, budget-fast and fallback
            ticks at their real frequency) and the rows the budgeted plan
            executes. Decode runs at its own serving-shaped width
            (decode_d/decode_d_expert).

Packed-plan occupancy rows: ``grid_tiles_padded`` is the PRE-packing static
grid (one padded run per lane plus the planned drop lane),
``grid_tiles_packed`` the packed plan's static bound (fused lane pairs for
the forward; E x C_fast slots for decode), ``occupied_tiles`` the tiles that
actually carried rows; ``planner_us`` isolates the jitted planner itself.
The bench-regression guard (benchmarks/check_regression.py, run by CI)
gates the deterministic rows — FLOP ratios, grid tiles, executed decode
rows — against the committed copy of this file's output.

Emits machine-readable ``BENCH_moe_path.json`` next to the cwd (or --out)
so CI can track the numbers over time. On CPU the pallas kernels run in
interpret mode — absolute us are a correctness-path baseline there; the
row/FLOP accounting is platform-independent.

Usage:  PYTHONPATH=src python -m benchmarks.moe_path [--smoke] [--out PATH]
                                                     [--sharded-devices N]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time


def _timeit(fn, iters: int = 3) -> float:
    fn()  # compile / warm
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters * 1e6


def run(smoke: bool = True, out: str = "BENCH_moe_path.json") -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import MoEConfig
    from repro.core import moe as MOE
    from repro.core.go_cache import go_cache_init, go_cache_step
    from repro.core.grouping import default_groups, group_of_expert_from_groups
    from repro.kernels.ops import go_selected_ffn, plan_tile_dispatch
    from repro.launch.mesh import make_mesh

    if smoke:
        T, d, E, k, g, de, bn, steps, B = 128, 64, 8, 2, 2, 64, 8, 8, 16
        d_dec, de_dec = 128, 128     # decode rows at a serving-shaped width
    else:
        T, d, E, k, g, de, bn, steps, B = 1024, 256, 16, 4, 2, 256, 128, 32, 32
        d_dec, de_dec = d, de

    e_xla = MoEConfig(num_experts=E, top_k=k, d_expert=de, group_size=g,
                      capacity_factor=2.0, backend="xla")
    e_pal = dataclasses.replace(e_xla, backend="pallas", gmm_block_rows=bn)
    key = jax.random.PRNGKey(0)
    params = MOE.moe_init(key, d, e_xla, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (T, d), jnp.float32) * 0.3
    goe = jnp.asarray(group_of_expert_from_groups(default_groups(e_xla)))

    f_xla = jax.jit(lambda x: MOE.group_forward(params, x, e_xla, goe)[0])
    f_pal = jax.jit(lambda x: MOE.group_forward(params, x, e_pal, goe)[0])
    us_xla = _timeit(lambda: f_xla(x).block_until_ready())
    us_pal = _timeit(lambda: f_pal(x).block_until_ready())

    # FFN-row accounting: the xla masked loop runs every group member over
    # the WHOLE pooled group buffer; pallas computes each pair's tile once
    # (a fused pair's straddle tile runs one extra masked pass — counted).
    N = T * k
    G = E // g
    C_exp = max(1, math.ceil(T * k / E * e_xla.capacity_factor))
    C_grp = max(1, math.ceil(g * C_exp * 0.7))
    rows_xla = g * G * C_grp                     # g member passes x G*C_grp
    from repro.core.routing import token_choice
    r = token_choice(x, params["gate"], k)
    ef = r.expert_idx.reshape(-1).astype(jnp.int32)
    # the SAME group-major fused plan group_forward's pallas branch builds
    members = MOE._members_matrix(goe, G, g)
    _, rank_of_expert, fuse = MOE.group_lane_map(members, g)
    plan = plan_tile_dispatch(rank_of_expert[ef], E, bn, fuse=fuse)
    tv = np.asarray(plan.tile_valid)
    straddle = np.asarray(plan.tile_expert2) != np.asarray(plan.tile_expert)
    rows_pal = int(bn * (tv.sum() + (tv & straddle).sum()))
    # static grids: pre-packing worst case (one padded run per lane + the
    # planned drop lane) vs the packed plan's fused bound
    plan_unfused = plan_tile_dispatch(rank_of_expert[ef], E, bn)
    grid_padded_fwd = plan_unfused.n_tiles
    grid_packed_fwd = plan.n_tiles
    # planner cost, isolated: the packed plan alone, jitted
    plan_jit = jax.jit(lambda ef: plan_tile_dispatch(ef, E, bn, fuse=fuse))
    us_plan_fwd = _timeit(
        lambda: jax.block_until_ready(plan_jit(ef)))

    # --- sharded forward: EP shard_map, per-shard buffers vs per-shard plans
    n_dev = jax.device_count()
    M = 2 if (n_dev >= 2 and E % 2 == 0) else 0
    if M:
        mesh = make_mesh((n_dev // M, M), ("data", "model"))
        B_ep = 4
        S = T // B_ep
        h = x.reshape(B_ep, S, d)
        f_ep_x = jax.jit(lambda h: MOE.moe_forward_ep(params, h, e_xla))
        f_ep_p = jax.jit(lambda h: MOE.moe_forward_ep(params, h, e_pal))
        with jax.set_mesh(mesh):
            us_ep_x = _timeit(lambda: f_ep_x(h)[0].block_until_ready())
            us_ep_p = _timeit(lambda: f_ep_p(h)[0].block_until_ready())
            a_x = f_ep_x(h)[1]
            a_p = f_ep_p(h)[1]
        assert int(a_x["dropped"]) == int(a_p["dropped"]), \
            "sharded xla vs pallas drop sets diverged"
        # rows: xla fills every shard's [E_loc, C] buffer (B*E*C total);
        # pallas computes each shard's tile-padded local runs
        C_ep = max(1, math.ceil(S * k / E * e_xla.capacity_factor))
        from repro.core.routing import token_choice as tc
        cnt_ep = np.stack([np.bincount(
            np.asarray(tc(h[b], params["gate"], k).expert_idx).reshape(-1),
            minlength=E) for b in range(B_ep)])
        rows_ep_x = B_ep * E * C_ep
        rows_ep_p = int(((cnt_ep + bn - 1) // bn * bn).sum())
        n_pairs_ep = B_ep * S * k
        sharded = {
            "mesh": {"data": n_dev // M, "model": M},
            "us_xla": round(us_ep_x, 1),
            "us_pallas": round(us_ep_p, 1),
            "routed_pairs": n_pairs_ep,
            "ffn_rows_xla": rows_ep_x,
            "ffn_rows_pallas": rows_ep_p,
            "redundant_flop_ratio_xla": round(rows_ep_x / n_pairs_ep, 3),
            "redundant_flop_ratio_pallas": round(rows_ep_p / n_pairs_ep, 3),
            "dropped": int(a_p["dropped"]),
        }
    else:
        sharded = {"skipped": f"needs >= 2 devices with E % 2 == 0 "
                              f"(have {n_dev} devices, E={E})"}

    # --- GO-cache decode: dense all-experts vs selected-only grouped GEMM
    # (the static-capacity decode plan with the router's top_k as the
    # occupancy hint — what blocks.py wires into the serving engine).
    # Decode gets its own serving-shaped widths (d_dec/de_dec): at the tiny
    # forward-smoke width the step is pure dispatch overhead and the row
    # savings are invisible.
    e_dec = dataclasses.replace(e_xla, d_expert=de_dec)
    params_dec = MOE.moe_init(jax.random.PRNGKey(5), d_dec, e_dec,
                              jnp.float32)
    cache = go_cache_init(B, E, k, d_dec, jnp.float32)
    gate = params_dec["gate"]
    dense_fn = lambda xt: MOE.expert_ffn_all(params_dec, xt)
    sel_fn = lambda xt, sel, gg: go_selected_ffn(
        xt, sel, gg, params_dec["experts"], E, bn=bn, topk_hint=k)[0]

    step_dense = jax.jit(lambda c, xt, t: go_cache_step(
        c, xt, t, gate, dense_fn))
    step_sel = jax.jit(lambda c, xt, t: go_cache_step(
        c, xt, t, gate, contrib_fn=sel_fn))

    # warm the cache so selection is sparse (empty cache selects everything)
    xs = jax.random.normal(jax.random.PRNGKey(2), (steps + k, B, d_dec)) * 0.3
    for t in range(k):
        cache = step_dense(cache, xs[t], t).cache

    # read the budget off the plan go_selected_ffn ACTUALLY builds (the
    # auto-resolved executor decides how bn rounds it)
    g0 = jax.nn.softmax(
        xs[k].astype(jnp.float32) @ gate.astype(jnp.float32), axis=-1)
    sel0 = step_dense(cache, xs[k], k).selected
    _, gplan = go_selected_ffn(xs[k], sel0, g0, params_dec["experts"], E,
                               bn=bn, topk_hint=k)
    C_fast = gplan.C_fast
    sel_pairs = 0
    sel_rows = 0                     # rows the budgeted plan executes
    fallbacks = 0
    c_d, c_s = cache, cache
    for t in range(k, k + steps):
        res = step_dense(c_d, xs[t], t)
        c_d = res.cache
        n_sel = int(res.selected.sum())
        over = int(np.asarray(res.selected).sum(0).max()) > C_fast
        fallbacks += over
        sel_pairs += n_sel
        sel_rows += E * (B if over else C_fast)
    for t in range(k, k + steps):
        c_s = step_sel(c_s, xs[t], t).cache
    assert np.allclose(np.asarray(c_d.outputs), np.asarray(c_s.outputs),
                       atol=1e-5), "dense vs selected decode diverged"

    # per-step time averaged over the WHOLE tick sequence, so budget-fast
    # and fallback ticks weigh in at their real frequency
    def _loop(stepfn):
        def go():
            c = cache
            for t in range(k, k + steps):
                c = stepfn(c, xs[t], t).cache
            jax.block_until_ready(c.outputs)
        return go
    us_dense = _timeit(_loop(step_dense), iters=5) / steps
    us_sel = _timeit(_loop(step_sel), iters=5) / steps

    # decode planner alone: counts + the one top_k that recovers the packed
    # gather (the persistent plan's only per-tick work), jitted
    def _decode_plan(sel):
        selT = sel.T
        ar = jnp.arange(B, dtype=jnp.int32)
        keys_ = jnp.where(selT, B - ar[None, :], -1 - ar[None, :])
        return selT.sum(1), jax.lax.top_k(keys_, C_fast)[1]
    dplan_jit = jax.jit(_decode_plan)
    us_plan_dec = _timeit(lambda: jax.block_until_ready(dplan_jit(sel0)))

    # old pre-packing decode grid: every (token, expert) pair planned as a
    # real row, plus the per-lane padding and the planned drop lane
    grid_padded_dec = math.ceil((B * E + (E + 1) * bn) / bn)
    grid_packed_dec = gplan.n_tiles_fast

    report = {
        "host_backend": jax.default_backend(),
        "config": {"T": T, "d": d, "E": E, "k": k, "g": g, "d_expert": de,
                   "bn": bn, "decode_batch": B, "decode_steps": steps,
                   "decode_d": d_dec, "decode_d_expert": de_dec},
        "forward": {
            "us_xla_masked": round(us_xla, 1),
            "us_pallas": round(us_pal, 1),
            "routed_pairs": N,
            "ffn_rows_xla_masked": rows_xla,
            "ffn_rows_pallas": rows_pal,
            "redundant_flop_ratio_xla": round(rows_xla / N, 3),
            "redundant_flop_ratio_pallas": round(rows_pal / N, 3),
            "grid_tiles_padded": grid_padded_fwd,
            "grid_tiles_packed": grid_packed_fwd,
            "occupied_tiles": int(plan.occupied),
        },
        "forward_sharded": sharded,
        "decode": {
            "us_step_dense": round(us_dense, 1),
            "us_step_selected": round(us_sel, 1),
            "rows_dense_per_steps": steps * B * E,
            "rows_selected_per_steps": sel_rows,
            "selected_pairs_per_steps": sel_pairs,
            "row_ratio_dense_over_selected": round(
                steps * B * E / max(1, sel_rows), 2),
            "grid_tiles_padded": grid_padded_dec,
            "grid_tiles_packed": grid_packed_dec,
            "budget_rows_per_lane": C_fast,
            "budget_fallback_steps": fallbacks,
        },
        "planner_us": {
            "forward_plan": round(us_plan_fwd, 1),
            "decode_plan": round(us_plan_dec, 1),
        },
    }
    if out:
        with open(out, "w") as f:
            json.dump(report, f, indent=2)
    return report


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default="BENCH_moe_path.fresh.json",
                    help="output path; the default is a gitignored FRESH "
                         "report — point it at BENCH_moe_path.json only to "
                         "deliberately re-baseline the CI regression gate")
    ap.add_argument("--sharded-devices", type=int, default=4,
                    help="force this many host devices (XLA_FLAGS, set "
                         "before jax imports) so the sharded-forward row "
                         "runs on single-device CPU hosts; 0 = don't force")
    args = ap.parse_args()
    if args.sharded_devices > 1 and "jax" not in sys.modules:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count="
              f"{args.sharded_devices}").strip()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    rep = run(smoke=args.smoke, out=args.out)
    f, sh, dck = rep["forward"], rep["forward_sharded"], rep["decode"]
    pl = rep["planner_us"]
    print(f"forward: xla {f['us_xla_masked']:.0f}us "
          f"(FLOP ratio {f['redundant_flop_ratio_xla']:.2f}x) vs "
          f"pallas {f['us_pallas']:.0f}us "
          f"(ratio {f['redundant_flop_ratio_pallas']:.2f}x, grid "
          f"{f['grid_tiles_packed']}/{f['grid_tiles_padded']} tiles, "
          f"{f['occupied_tiles']} occupied)")
    if "skipped" in sh:
        print(f"sharded: skipped — {sh['skipped']}")
    else:
        print(f"sharded: mesh {sh['mesh']} xla {sh['us_xla']:.0f}us "
              f"(ratio {sh['redundant_flop_ratio_xla']:.2f}x) vs "
              f"pallas {sh['us_pallas']:.0f}us "
              f"(ratio {sh['redundant_flop_ratio_pallas']:.2f}x)")
    print(f"decode:  dense {dck['us_step_dense']:.0f}us/"
          f"{dck['rows_dense_per_steps']} rows vs selected "
          f"{dck['us_step_selected']:.0f}us/{dck['rows_selected_per_steps']} "
          f"rows ({dck['row_ratio_dense_over_selected']:.1f}x fewer, grid "
          f"{dck['grid_tiles_packed']}/{dck['grid_tiles_padded']} tiles, "
          f"{dck['budget_fallback_steps']} fallback ticks)")
    print(f"planner: forward {pl['forward_plan']:.0f}us, "
          f"decode {pl['decode_plan']:.0f}us")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
