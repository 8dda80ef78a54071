"""Bring-up check: the continuous-batching serving path on the chip.

    python chip_smoke.py              # one TPU: the two phases below
    python chip_smoke.py --chips 4    # four TPUs: the sharded phase alone

One chip, one process:
  granite  granite-moe-3b-a800m at its published config (32 layers,
           d=1536, 40 experts top-8, bf16), random weights from SEED,
           serving 8 requests (64-256 prompt tokens, 32 generated each)
           through `serve_continuous` -> ServingEngine on the paged pool,
           4 slots, chunked prefill for prompts over 128 tokens.
  gocache  llama_moe_4_16 at full width (d=4096) with its depth cut to 8
           layers: the paper's expert-choice GO-cache decode, whose
           selected-expert FFN runs through `go_selected_ffn` on the
           grouped-GEMM kernels.

Four chips (--chips 4):
  sharded  deepseek-moe-16b at its published widths and depth, parameters
           created already sharded (experts over the "model" axis of a
           1x4 mesh), serving 4 requests through ServingEngine(mesh=) with
           chunked prefill (expert-parallel shard_map under the mesh); then
           the same config cut to 2 layers runs teacher-forced (one one-shot
           and one chunked prefill) on the mesh and on devices()[0] in this
           process, and the logits must agree to F32_PARITY. That comparison
           overrides three settings of the served config: float32 weights
           and activations, the "highest" matmul precision, and capacity
           factor E/k (see sharded_phase).

Every phase fails unless: the chip paths resolved (MoE backend "pallas",
paged attention "kernel", no interpret mode, `tpu_custom_call` in the
lowered decode step); every request ended DONE with 0 tick retries; all
logits are finite; and teacher-forced logits (prefill, then the first
decode steps fed the served tokens) of one bucket-exact one-shot prompt
and one prompt prefilled in chunks (paged_attn_chunk on the kernel path,
last chunk padded) sit no further from the reference
than PARITY_FACTOR times the reference's own bf16 rounding error (relative
L2 per logit row, against its f32 evaluation). One-chip phases compare against
the program's plain path on the same chip: MoE backend "xla", paged
attention "gather". The sharded phase compares the mesh with one chip.
Wall time, compile time and peak device bytes are printed per phase as
labels of this run, not as metrics.

The last stdout line is {"ok": true, "device": {...}}; any failure exits
non-zero without it. The script needs the repository around it (src/) and
an accelerator: it exits non-zero when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.registry import get_config  # noqa: E402
from repro.core.moe import resolve_backend  # noqa: E402
from repro.kernels import paged_attn  # noqa: E402
from repro.kernels.moe_gmm import default_interpret  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.serve import init_params, serve_continuous  # noqa: E402
from repro.models.model import (init_decode_state, prefill,  # noqa: E402
                                prefill_chunk, serve_step,
                                write_decode_slot)
from repro.serving import engine as ENG  # noqa: E402

# The chip path and its reference round bf16 at different points (fused f32
# accumulators vs per-op bf16 outputs), and over a deep random-weight stack
# such differences grow and flip near-tied expert choices. So the limit is
# relative to the rounding error of the reference itself: the chip path may
# sit at most PARITY_FACTOR times as far from the reference as the reference
# sits from its own f32 evaluation (same bf16 weights, f32 activations,
# highest matmul precision). A wrong kernel lands ~1 (unrelated logits).
PARITY_FACTOR = 2.0
PARITY_STEPS = 4
SEED = 0
SLOTS, PAGE, CHUNK = 4, 16, 128
# the sharded comparison's cut: 2 float32 layers of deepseek-moe-16b are
# ~6.3 GB of weights, which one chip holds. A wrong kernel or sharding rule
# lands ~1 from the one-chip logits, f32 summation order ~1e-6 (6e-7 to 8e-7
# measured on four v5e chips), and an f32 matmul rounded through bf16 (the
# TPU's default precision) ~5e-3 (4e-3 to 5e-3 measured there)
SHARDED_CUT_LAYERS = 2
F32_PARITY = 1e-4
# prompt lengths: bucket-exact one-shot prefills (64, 128) and chunked
# ones (> 128, in 128-token chunks) — three prefill programs in all
LENGTHS = (128, 128, 64, 200, 64, 256, 128, 160)
# the prompts held to the reference: one one-shot (128), one chunked (200)
PARITY_PROMPTS = (0, 3)


class CompileClock:
    """Backend compile seconds and persistent-cache hits/misses, read
    from jax.monitoring events."""

    def __init__(self):
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, ev, secs, **_):
        if ev == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _ev(self, ev, **_):
        if ev == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif ev == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.seconds, self.hits, self.misses


def reference_cfg(cfg):
    """The program's own plain path: no Pallas kernels anywhere."""
    return cfg.with_overrides(paged_attn="gather",
                              moe=dataclasses.replace(cfg.moe, backend="xla"))


def make_prompts(seed: int, lengths, vocab: int) -> list:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n, dtype=np.int32) for n in lengths]


def teacher_forced(params, cfg, prompts, forced, max_len: int, ps: int,
                   chunk: int):
    """Logits of `prompts` on a paged decode state laid out as the engine
    lays it out: prefill logits [B, V], then one decode step per column of
    `forced` [B, n] -> [B, n, V]. A prompt longer than `chunk` prefills as
    the engine's chunk job does: chunk by chunk through a batch-1 view of
    the pool, straight into its pages, the last chunk padded."""
    B, P = len(prompts), max_len // ps
    pre_fn = jax.jit(prefill, static_argnames=("cfg", "max_len"))
    chunk_fn = jax.jit(prefill_chunk, static_argnames="cfg")
    write_fn = jax.jit(write_decode_slot)
    step_fn = jax.jit(serve_step, static_argnames="cfg")
    state = init_decode_state(cfg, B, max_len, per_slot_t=True,
                              paged=(1 + B * P, ps))
    pre = []
    for b, p in enumerate(prompts):
        pages = jnp.arange(1 + b * P, 1 + (b + 1) * P, dtype=jnp.int32)
        if len(p) > chunk:
            src = init_decode_state(cfg, 1, max_len, paged=(1, ps))
            src["block_table"] = pages[None]
            padded = np.pad(p, (0, -len(p) % chunk))
            for pos in range(0, len(p), chunk):
                src["k_pages"], src["v_pages"] = (state["k_pages"],
                                                  state["v_pages"])
                src, lg = chunk_fn(params, src,
                                   jnp.asarray(padded[pos:pos + chunk])[None],
                                   cfg, jnp.int32(pos),
                                   jnp.int32(min(chunk, len(p) - pos)))
                state["k_pages"], state["v_pages"] = (src.pop("k_pages"),
                                                      src.pop("v_pages"))
        else:
            src, lg = pre_fn(params, jnp.asarray(p)[None], cfg, {}, max_len)
        pre.append(lg[0])
        state = write_fn(state, b, src, pages)
    steps = []
    for s in range(forced.shape[1]):
        lg, state = step_fn(params, state, jnp.asarray(forced[:, s]), cfg)
        steps.append(lg)
    return (np.asarray(jnp.stack(pre), np.float32),
            np.asarray(jnp.stack(steps, axis=1), np.float32))


def rel_err(got, ref) -> float:
    """Worst relative L2 error over logit rows (last axis)."""
    num = np.linalg.norm(got - ref, axis=-1)
    den = np.maximum(np.linalg.norm(ref, axis=-1), 1e-30)
    return float((num / den).max())


def f32_logits(params, cfg, *args):
    """Teacher-forced logits of the plain path in f32 arithmetic over the
    same bf16 weights: the yardstick for bf16 rounding."""
    with jax.default_matmul_precision("highest"):
        return teacher_forced(
            params, reference_cfg(cfg).with_overrides(dtype="float32"), *args)


def bf16_limits(ref, exact) -> list:
    """Parity limits for (prefill, decode) logits: PARITY_FACTOR times how
    far the bf16 reference `ref` sits from its f32 evaluation `exact`."""
    if not all(np.isfinite(a).all() for a in exact):
        raise AssertionError("non-finite f32 reference logits")
    floors = [rel_err(r, e) for r, e in zip(ref, exact)]
    print(f"  reference vs its f32 evaluation: prefill {floors[0]!r}, "
          f"decode {floors[1]!r}")
    return [PARITY_FACTOR * max(f, 1e-3) for f in floors]


def check_parity(tag: str, got, ref, limits) -> None:
    """`got` vs `ref` per logit row, for (prefill, decode) logits, each
    within its limit."""
    failed = []
    for i, name in enumerate(("prefill", "decode")):
        if not (np.isfinite(got[i]).all() and np.isfinite(ref[i]).all()):
            raise AssertionError(f"{tag}: non-finite {name} logits")
        err = rel_err(got[i], ref[i])
        print(f"  {tag} {name} logits: rel L2 err {err!r}; limit "
              f"{limits[i]!r}")
        if err > limits[i]:
            failed.append(f"{name} {err!r} > {limits[i]!r}")
    if failed:
        raise AssertionError(f"{tag}: " + "; ".join(failed))


def check_chip_paths(cfg, eng) -> None:
    """The served config resolved to the chip's kernels, and the engine's
    compiled decode tick holds a Mosaic kernel."""
    got = {"moe backend": resolve_backend(cfg.moe),
           "paged attention": paged_attn.resolve_mode(cfg),
           "interpret": default_interpret()}
    want = {"moe backend": "pallas", "paged attention": "kernel",
            "interpret": False}
    print(f"  resolved paths {got}")
    if got != want:
        raise AssertionError(f"resolved {got}, want {want}")
    pool = eng.pool
    with eng._mesh_scope():
        hlo = ENG._decode_step.lower(
            eng.params, pool.state, jnp.asarray(pool.pending),
            jnp.asarray(pool.active_mask()), eng.cfg).as_text()
    n = hlo.count("tpu_custom_call")
    print(f"  decode step lowers with {n} tpu_custom_call sites")
    if n == 0:
        raise AssertionError("decode step holds no tpu_custom_call")


def serve_and_check(params, cfg, prompts, gen: int, *, slots: int, ps: int,
                    chunk: int, mesh=None) -> dict:
    res = serve_continuous(params, cfg, prompts, gen, num_slots=slots,
                           paged=True, page_size=ps, prefill_chunk=chunk,
                           prompt_buckets=True, mesh=mesh)
    s = res["stats"]
    print(f"  served {s['finished']} requests in {s['steps']} ticks "
          f"({s['chunk_ticks']} chunk ticks), statuses {s['statuses']}, "
          f"tick retries {s['tick_retries']}, mesh {s['mesh']}")
    if s["statuses"] != {"DONE": len(prompts)}:
        raise AssertionError(f"statuses {s['statuses']}")
    if s["tick_retries"]:
        raise AssertionError(f"{s['tick_retries']} tick retries")
    if s["chunk_ticks"] == 0 and chunk and max(map(len, prompts)) > chunk:
        raise AssertionError("no chunked-prefill tick ran")
    for rid, toks in res["tokens"].items():
        if len(toks) != gen:
            raise AssertionError(f"request {rid}: {len(toks)} tokens")
    return res


def phase_report(name: str, t0: float, clock: CompileClock, c0) -> None:
    secs, hits, misses = clock.snapshot()
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print(f"[{name}] wall {time.perf_counter() - t0!r} s, backend compile "
          f"{secs - c0[0]!r} s, cache hits {hits - c0[1]} misses "
          f"{misses - c0[2]}, device 0 peak bytes in use (process so far) "
          f"{peak}")


def serving_phase(name: str, cfg, *, lengths, gen: int, seed: int,
                  clock: CompileClock) -> None:
    """Serve `lengths` prompts through the engine on one device, then hold
    the PARITY_PROMPTS' teacher-forced logits to the plain path."""
    print(f"== phase {name}")
    t0, c0 = time.perf_counter(), clock.snapshot()
    params = init_params(jax.random.PRNGKey(seed), cfg)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"  {cfg.name}: {cfg.num_layers} layers, d={cfg.d_model}, "
          f"{cfg.moe.num_experts} experts top-{cfg.moe.top_k}, "
          f"{cfg.dtype}, {n_params} parameters")
    prompts = make_prompts(seed, lengths, cfg.vocab_size)
    res = serve_and_check(params, cfg, prompts, gen, slots=SLOTS, ps=PAGE,
                          chunk=CHUNK)
    eng = res["engine"]
    check_chip_paths(eng.cfg, eng)
    rids = list(res["tokens"])
    forced = np.stack([res["tokens"][rids[i]][:PARITY_STEPS]
                       for i in PARITY_PROMPTS])
    args = ([prompts[i] for i in PARITY_PROMPTS], forced,
            eng.pool.max_tokens, PAGE, CHUNK)
    got = teacher_forced(params, eng.cfg, *args)
    ref = teacher_forced(params, reference_cfg(eng.cfg), *args)
    check_parity(f"{name} vs plain path", got, ref,
                 bf16_limits(ref, f32_logits(params, eng.cfg, *args)))
    phase_report(name, t0, clock, c0)


def sharded_phase(*, clock: CompileClock) -> None:
    """deepseek-moe-16b over a 1x4 mesh, experts over "model"."""
    print("== phase sharded")
    t0, c0 = time.perf_counter(), clock.snapshot()
    cfg = get_config("deepseek-moe-16b")
    mesh = make_mesh((1, 4), ("data", "model"))
    params = init_params(jax.random.PRNGKey(SEED), cfg, mesh)
    wg = params["layers"]["moe"]["experts"]["wg"]
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"  {cfg.name}: {cfg.num_layers} layers, d={cfg.d_model}, "
          f"{cfg.moe.num_experts} experts top-{cfg.moe.top_k}, "
          f"{n_params} parameters; expert bank {wg.shape} sharded "
          f"{wg.sharding.spec}, {wg.addressable_shards[0].data.nbytes} "
          f"bytes per device")
    if wg.sharding.spec[1] != "model":
        raise AssertionError(f"experts not over 'model': {wg.sharding.spec}")
    prompts = make_prompts(SEED, (64, 128, 96, 128), cfg.vocab_size)
    res = serve_and_check(params, cfg, prompts, 16, slots=SLOTS, ps=PAGE,
                          chunk=64, mesh=mesh)
    check_chip_paths(res["engine"].cfg, res["engine"])
    del params, res

    # The comparison runs in float32 at the highest matmul precision, where
    # the mesh program and the one-chip program compute one function to f32
    # accuracy: at the TPU's default precision an XLA f32 matmul rounds its
    # inputs to bf16. In bf16 the two programs differ by where XLA rounds:
    # the unsharded program keeps some intermediates in f32 across fused
    # ops, the sharded one rounds them at its collectives (~3x the bf16
    # error of the unsharded program, measured on a CPU mesh), so the served
    # bf16 mesh program is held to DONE/finite checks only.
    # Capacity E/k lets every expert take every token: the mesh's expert-
    # parallel dispatch then drops nothing, like the one-chip plan.
    e = cfg.moe
    cut = cfg.with_overrides(num_layers=SHARDED_CUT_LAYERS, dtype="float32",
                             moe=dataclasses.replace(
                                 e, capacity_factor=e.num_experts / e.top_k))
    print(f"  comparison: {cut.name} cut to {cut.num_layers} layers in "
          f"float32 (capacity factor {cut.moe.capacity_factor}: no "
          f"expert-parallel drops), teacher-forced on the 1x4 mesh, then on "
          f"devices()[0]")
    params = init_params(jax.random.PRNGKey(SEED + 1), cut, mesh)
    forced = np.stack([p[:PARITY_STEPS] for p in prompts[:2]])
    args = (prompts[:2], forced, 256, PAGE, 64)   # 64 one-shot, 128 chunked
    with jax.default_matmul_precision("highest"):
        with jax.set_mesh(mesh):
            on_mesh = teacher_forced(params, cut, *args)
        one = jax.device_put(params, jax.devices()[0])
        del params
        on_one = teacher_forced(one, cut, *args)
    check_parity("mesh vs one chip", on_mesh, on_one, (F32_PARITY,) * 2)
    phase_report("sharded", t0, clock, c0)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: the sharded phase alone")
    args = ap.parse_args()

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}")
    if info["platform"] != "tpu":
        print("chip_smoke: no TPU found", file=sys.stderr)
        return 2
    if info["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {info['count']}", file=sys.stderr)
        return 2

    print(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    if args.chips == 4:
        sharded_phase(clock=clock)
    else:
        granite = get_config("granite-moe-3b-a800m")
        serving_phase("granite", granite, seed=SEED, clock=clock,
                      lengths=LENGTHS, gen=32)
        llama = get_config("llama_moe_4_16")
        cut = llama.with_overrides(num_layers=8)
        print(f"gocache: {llama.name} depth cut {llama.num_layers} -> "
              f"{cut.num_layers} layers (full width d={cut.d_model}): 32 "
              f"bf16 layers are ~13.5 GB of weights alone on a 16 GB chip")
        serving_phase("gocache", cut, seed=SEED + 1, clock=clock,
                      lengths=LENGTHS, gen=32)
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
