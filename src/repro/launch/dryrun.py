import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input shape x mesh) cell:
  jax.jit(entry, in_shardings, out_shardings).lower(**specs).compile()
then record memory_analysis / cost_analysis / collective bytes (parsed from
the post-GSPMD HLO) into artifacts/dryrun/<arch>_<shape>_<mesh>.json — the
roofline table (benchmarks/roofline.py) reads these artifacts.

Usage:
  python -m repro.launch.dryrun --arch deepseek-moe-16b --shape train_4k
  python -m repro.launch.dryrun --arch gemma3-27b --shape decode_32k --multi-pod
  python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import json
import re
import time
import traceback

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES
from repro.configs.registry import get_config, list_archs, shape_cells
from repro.launch import sharding as SH
from repro.launch import specs as SPEC
from repro.launch.hlo_cost import analyze as hlo_analyze, xla_cost_dict
from repro.launch.mesh import dp_axes, make_production_mesh

ART_DIR = os.path.join(os.path.dirname(__file__), "../../../artifacts/dryrun")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16}

_COLL_RE = re.compile(
    r"=\s+(?:\()?([a-z0-9]+)\[([0-9,]*)\][^)]*?\s"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"[\s(]")


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-tensor bytes per collective kind (all-reduce counted 2x for
    the ring's reduce+broadcast phases). Approximates per-device ICI bytes."""
    out = {"all-gather": 0, "all-reduce": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0}
    for m in _COLL_RE.finditer(hlo_text):
        dt, dims, kind = m.groups()
        if dt not in _DTYPE_BYTES:
            continue
        n = _DTYPE_BYTES[dt]
        for d in dims.split(","):
            if d:
                n *= int(d)
        out[kind] += n * (2 if kind == "all-reduce" else 1)
    out["total"] = sum(out.values())
    return out


def _sharded_specs(cfg, shape, mesh, *, policy: str = "tp",
                   micro_global: int = 0):
    """(kwargs of ShapeDtypeStructs, in_shardings pytree, entry_fn)."""
    specs = SPEC.input_specs(cfg, shape, micro_global=micro_global)
    mode = "train" if shape.kind == "train" else "serve"
    if policy == "dp_only":
        mode += "_dp"
    p_sh = SH.param_shardings(specs["params"], cfg, mesh, mode)
    if shape.kind == "train":
        o_sh = SH.opt_shardings(specs["opt_state"], p_sh)
        b_sh = SH.batch_shardings(specs["batch"], mesh, policy)
        fn = SPEC.make_train_step(cfg)
        return specs, (p_sh, o_sh, b_sh), fn, ("params", "opt_state", "batch")
    if shape.kind == "prefill":
        dp = dp_axes(mesh)
        t_sh = NamedSharding(mesh, P(dp, None))
        e_sh = SH.state_shardings(specs["extras"], cfg, mesh,
                                  shape.global_batch)
        fn = SPEC.make_prefill_step(cfg)
        return specs, (p_sh, t_sh, e_sh), fn, ("params", "tokens", "extras")
    dp = dp_axes(mesh)
    dp_n = 1
    for a in dp:
        dp_n *= mesh.shape[a]
    s_sh = SH.state_shardings(specs["state"], cfg, mesh, shape.global_batch)
    t_sh = NamedSharding(
        mesh, P(dp) if shape.global_batch % dp_n == 0 else P(None))
    fn = SPEC.make_serve_step(cfg)
    return specs, (p_sh, s_sh, t_sh), fn, ("params", "state", "tokens")


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             smoke: bool = False, save: bool = True, *,
             policy: str = "tp", micro_global: int = 0,
             cfg_overrides: dict | None = None, variant: str = "") -> dict:
    cfg = get_config(arch, smoke=smoke)
    if cfg_overrides:
        cfg = cfg.with_overrides(**cfg_overrides)
    shape = SHAPES[shape_name]
    mesh_tag = "2pod" if multi_pod else "1pod"
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    with jax.set_mesh(mesh):
        specs, in_sh, fn, order = _sharded_specs(
            cfg, shape, mesh, policy=policy, micro_global=micro_global)
        args = [specs[k] for k in order]
        # donation mirrors production: train donates (params, opt_state);
        # decode donates the serving state (KV/GO caches update in place)
        donate = (0, 1) if shape.kind == "train" else \
                 ((1,) if shape.kind == "decode" else ())
        lowered = jax.jit(fn, in_shardings=in_sh,
                          donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = xla_cost_dict(compiled)
        hlo_text = compiled.as_text()
        loop_aware = hlo_analyze(hlo_text)   # trip-count-corrected totals

    n_dev = mesh.devices.size
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_tag,
        "variant": variant or "baseline",
        "policy": policy,
        "devices": n_dev,
        "kind": shape.kind,
        "seq_len": shape.seq_len,
        "global_batch": shape.global_batch,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        # loop-aware (while bodies x known_trip_count) — the roofline inputs
        "flops_per_device": loop_aware["flops"],
        "bytes_per_device": loop_aware["bytes"],
        "collective_bytes_per_device": loop_aware["collectives"],
        # raw XLA numbers (loop bodies counted once) kept for reference
        "raw_cost_analysis": {
            "flops": cost.get("flops", -1.0),
            "bytes_accessed": cost.get("bytes accessed", -1.0),
        },
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", -1),
            "output_bytes": getattr(mem, "output_size_in_bytes", -1),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", -1),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", -1),
            # CPU backend upcasts bf16 dot operands to f32 and hoists the
            # conversions (weight stacks, caches). A TPU target consumes
            # bf16 natively, so the corrected watermark excludes them.
            "cpu_upcast_bytes": loop_aware["cpu_upcast_bytes"],
            "temp_bytes_tpu_corrected": max(
                0, getattr(mem, "temp_size_in_bytes", 0)
                - loop_aware["cpu_upcast_bytes"]),
        },
    }
    if save:
        os.makedirs(ART_DIR, exist_ok=True)
        suffix = f"_{variant}" if variant else ""
        path = os.path.join(
            ART_DIR, f"{arch}_{shape_name}_{mesh_tag}{suffix}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in list_archs():
            for s in shape_cells(a):
                cells.append((a, s.name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    failures = []
    for arch, shp in cells:
        try:
            rec = run_cell(arch, shp, multi_pod=args.multi_pod,
                           smoke=args.smoke)
            print(f"OK   {arch:22s} {shp:12s} {rec['mesh']} "
                  f"flops/dev={rec['flops_per_device']:.3e} "
                  f"mem_temp={rec['memory']['temp_bytes']/2**30:.2f}GiB "
                  f"(tpu~{rec['memory']['temp_bytes_tpu_corrected']/2**30:.2f}) "
                  f"coll={rec['collective_bytes_per_device']['total']/2**20:.1f}MiB "
                  f"(lower {rec['lower_s']}s compile {rec['compile_s']}s)",
                  flush=True)
        except Exception as e:
            failures.append((arch, shp, repr(e)))
            print(f"FAIL {arch:22s} {shp:12s}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} dry-run cells failed")


if __name__ == "__main__":
    main()
