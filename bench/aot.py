"""Compile a cell's programs at their real sizes for a described TPU v5e,
without the chip, and print each one's memory analysis.

    JAX_PLATFORMS=cpu python3 bench/aot.py --workload granite.chat

The programs are the ones the window drives: the decode tick over the whole
slot pool, one one-shot prefill per prompt bucket the traffic can produce,
and the chunked-prefill step. Nothing runs, so this says nothing about
times or results; it finds what the chip's compiler refuses and whether
weights, pages and a program's temporaries fit the chip's memory. A cell
over four chips is compiled for a described v5e:2x2 under the mesh and
layouts the run uses, and its numbers are each chip's. The
program resolves its kernels from the host's platform, so here its
lowering platform is set to the TPU's and the chip paths are named in the
configuration.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from bench import serve as SV  # noqa: E402
from bench import traffic as TR  # noqa: E402
from bench.run import reference  # noqa: E402
from bench.spec import load_cell  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--programs", default="decode,prefill,chunk")
    args = ap.parse_args()
    from jax.experimental import topologies

    from repro.kernels import moe_gmm, ops, paged_attn
    from repro.models import model as M
    from repro.serving import engine as ENG
    for mod in (moe_gmm, ops, paged_attn):
        mod.lowering_platform = lambda: "tpu"
    jax.config.update("jax_enable_compilation_cache", False)

    cell = load_cell(args.workload)
    ref = reference(cell.config)
    sz = ref.sizes(cell.config)
    cfg = SV.program_config(cell.config)
    cfg = cfg.with_overrides(paged_attn="kernel", moe=dataclasses.replace(
        cfg.moe, backend="pallas"))
    e = cell.traffic["engine"]
    slots, mt, ps, chunk = (e["slots"], e["max_tokens"], e["page_size"],
                            e["prefill_chunk"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    num_pages = e.get("num_pages") or slots * (mt // ps) + 1
    wshapes = jax.eval_shape(lambda: ref.make_weights(sz, 0))
    pshapes = jax.eval_shape(lambda: M.init_decode_state(
        cfg, slots, mt, per_slot_t=True, paged=(num_pages, ps)))
    if cell.chips == 1:
        one = SingleDeviceSharding(topo.devices[0])
        scope = contextlib.nullcontext()
        wsh = jax.tree.map(lambda _: one, wshapes)
        psh = jax.tree.map(lambda _: one, pshapes)
    else:
        # the cell's mesh over the described chips, laid out as the run
        # lays it out (bench/run.py)
        from repro.launch.mesh import make_mesh
        from repro.launch.sharding import serve_state_shardings
        mesh = make_mesh((1, cell.chips), ("data", "model"),
                         devices=topo.devices[:cell.chips])
        one = NamedSharding(mesh, P())
        scope = jax.set_mesh(mesh)
        wsh = SV.weight_shardings(wshapes, cfg, mesh)
        psh = serve_state_shardings(cfg, mesh, slots, mt,
                                    paged=(num_pages, ps))

    def shaped(tree, shardings=None):
        if shardings is None:
            shardings = jax.tree.map(lambda _: one, tree)
        return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=s), tree, shardings)

    params, pool = shaped(wshapes, wsh), shaped(pshapes, psh)
    nbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    pbytes = pool["k_pages"].size * 2 * 2
    print(f"{cell.name}: weights {nbytes / 1e9:.3f} GB, pages "
          f"{pbytes / 1e9:.3f} GB ({num_pages} pages), over "
          f"{cell.chips} chip(s); a program's numbers are one chip's",
          flush=True)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)  # noqa
    want = args.programs.split(",")

    def report(name, lowered):
        c = lowered.compile()
        m = c.memory_analysis()
        print(f"  {name}: args {m.argument_size_in_bytes / 1e9:.3f} GB, "
              f"out {m.output_size_in_bytes / 1e9:.3f} GB, temp "
              f"{m.temp_size_in_bytes / 1e9:.3f} GB, alias "
              f"{m.alias_size_in_bytes / 1e9:.3f} GB, kernels "
              f"{c.as_text().count('tpu_custom_call')}", flush=True)

    plan = TR.plan(cell.traffic, 0, 10.0, sz["vocab"])
    lens = sorted({len(r.prompt) for r in plan.requests})
    with scope:
        if "decode" in want:
            report("decode", ENG._decode_step.lower(
                params, pool, i32(slots),
                jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one),
                cfg))
        if "prefill" in want:
            for b in sorted({SV.prefill_bucket(n, mt) for n in lens
                             if n <= chunk}):
                report(f"prefill {b}", ENG._jit_prefill.lower(
                    params, i32(1, b), cfg, {}, mt, i32()))
        if "chunk" in want and max(lens) > chunk:
            st = shaped(jax.eval_shape(lambda: M.init_decode_state(
                cfg, 1, mt, paged=(1, ps))))
            st["k_pages"], st["v_pages"] = pool["k_pages"], pool["v_pages"]
            report(f"chunk {chunk}", ENG._jit_prefill_chunk.lower(
                params, st, i32(1, chunk), cfg, i32(), i32()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
