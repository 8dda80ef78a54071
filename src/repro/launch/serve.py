"""Serving drivers with KV + GO caches (the paper's generation path).

Two modes share the same compiled kernels:

  generate()        static batch — a fixed batch of requests moves lock-step
                    from prefill to completion. The reference semantics (and
                    the oracle the serving tests compare against).
  ServingEngine     continuous batching (repro/serving) — requests join
                    mid-flight into free slots of a pooled KV+GO cache and
                    retire on EOS/length; nothing stalls, nothing recompiles.
                    This is the default for the CLI below.

Flow per request, either way:
  1. prefill() — full-sequence pass fills the KV caches and, for
     expert-choice MoE, builds the per-layer GO caches (paper eq. 4-5);
  2. serve_step() per generated token — O(1) state growth: the gate sees ONE
     token, TopKUpdate against cached mins replaces at most one slot per
     expert, and only selecting experts' outputs are recomputed.

CPU-runnable with smoke configs:
  PYTHONPATH=src python -m repro.launch.serve --arch llama_moe_4_16 --smoke \
      --requests 8 --slots 4 --prompt 32 --gen 16
  # static-batch reference path:
  PYTHONPATH=src python -m repro.launch.serve --arch llama_moe_4_16 --smoke \
      --static --batch 4 --prompt 32 --gen 16
  # sharded: slot rows over the data axis, decode under a (2, 2) mesh:
  XLA_FLAGS=--xla_force_host_platform_device_count=4 PYTHONPATH=src \
      python -m repro.launch.serve --arch llama_moe_4_16 --smoke \
      --requests 8 --slots 4 --mesh-model 2
  # paged KV pool + chunked prefill (block tables; long prompts admitted
  # one page-granular chunk per tick):
  PYTHONPATH=src python -m repro.launch.serve --arch llama_moe_4_16 --smoke \
      --requests 8 --slots 4 --paged --page-size 16 --chunk-prefill 16
"""
from __future__ import annotations

import argparse
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.models.model import model_init, prefill, serve_step
from repro.serving import ServingEngine


def generate(params, cfg, prompts: jax.Array, gen_tokens: int,
             extras: dict | None = None, *, greedy: bool = True,
             key=None, max_len: int = 0) -> dict:
    """prompts [B, T] -> generated [B, gen_tokens] (+ stats). `max_len` sizes
    the KV/GO cache (0 -> prompt + gen + 1); pass the slot pool's max_tokens
    to compare bit-exactly against the continuous-batching engine."""
    B, T = prompts.shape
    state, logits = jax.jit(
        prefill, static_argnames=("cfg", "max_len"))(
            params, prompts, cfg, extras or {},
            max_len=max_len or (T + gen_tokens + 1))
    step = jax.jit(serve_step, static_argnames="cfg")

    out = []
    t0 = time.time()
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    for i in range(gen_tokens):
        out.append(tok)
        logits, state = step(params, state, tok, cfg)
        if greedy or key is None:
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(sub, logits).astype(jnp.int32)
    dt = time.time() - t0
    return {
        "tokens": jnp.stack(out, axis=1),
        "decode_s": dt,
        "tok_per_s": B * gen_tokens / dt,
        "state": state,
    }


def serve_continuous(params, cfg, prompts: list, gen_tokens: int, *,
                     num_slots: int, max_tokens: int = 0,
                     extras: dict | None = None,
                     arrival_steps: list | None = None, mesh=None,
                     temperature: float = 0.0, top_p: float = 1.0,
                     prompt_buckets: bool = False, paged: bool = False,
                     page_size: int = 16, num_pages: int | None = None,
                     kv_quant: str | None = None,
                     prefill_chunk: int = 0,
                     priorities: list | None = None,
                     preemption: bool = False, chaos=None,
                     deadline_s: float | None = None,
                     max_wall_s: float | None = None,
                     prefix_share: bool | None = None,
                     expert_aware: bool | None = None,
                     journal_dir: str | None = None,
                     snapshot_every: int = 0) -> dict:
    """Run a list of prompts through the continuous-batching engine.
    With `mesh`, slot rows are sharded across the data-parallel replicas and
    every decode tick runs under the mesh (launch/sharding.py rules).
    `temperature` > 0 samples with top-p nucleus filtering (per-request
    seeds derive from the request id); `prompt_buckets` pads prompts to
    power-of-two buckets so prefill compiles once per bucket. `paged` swaps
    the dense slot rows for the block-table page pool (`page_size`,
    `num_pages` — None keeps the dense token capacity); `prefill_chunk`
    admits long prompts one chunk per tick; `priorities` orders admission
    (lower = earlier, FIFO within a level). `preemption` lets a blocked
    higher-priority admission evict lower-priority streams (paged pools;
    evicted streams resume bit-identically); `chaos` injects seeded faults
    (serving/chaos.py); `deadline_s`/`max_wall_s` bound every request's
    wall clock (TIMEOUT past them). Requests that end in a non-DONE
    terminal status surface their partial streams. `prefix_share` maps
    prompts sharing a page-aligned prefix onto the same physical pages
    copy-on-write and skips the shared prefill (paged pools);
    `expert_aware` scores admission order by routing overlap with the
    active batch (MoE attention archs) — both default to the
    REPRO_PREFIX_SHARE / REPRO_EXPERT_AWARE env knobs. `journal_dir`
    journals every request lifecycle event and commits an atomic engine
    snapshot every `snapshot_every` ticks (paged pools;
    serving/journal.py) — a crashed run resumes bit-identically via
    ServingEngine.recover(journal_dir).
    Returns per-request token arrays plus engine stats."""
    max_tokens = max_tokens or (
        max(len(p) for p in prompts) + gen_tokens + 1)
    # the engine requires max_tokens to be page- and chunk-granular; round
    # the derived default up so the CLI knobs compose in any combination
    grain = math.lcm(page_size if paged else 1,
                     prefill_chunk if prefill_chunk else 1)
    max_tokens += -max_tokens % grain
    eng = ServingEngine(params, cfg, num_slots=num_slots,
                        max_tokens=max_tokens, extras=extras, mesh=mesh,
                        prompt_buckets=prompt_buckets, paged=paged,
                        page_size=page_size, num_pages=num_pages,
                        kv_quant=kv_quant,
                        prefill_chunk=prefill_chunk, preemption=preemption,
                        chaos=chaos, prefix_share=prefix_share,
                        expert_aware=expert_aware,
                        journal_dir=journal_dir or None,
                        snapshot_every=snapshot_every)
    ids = []
    for i, p in enumerate(prompts):
        step = arrival_steps[i] if arrival_steps else 0
        ids.append(eng.submit(p, gen_tokens, extras=extras,
                              arrival_step=step, temperature=temperature,
                              top_p=top_p,
                              priority=priorities[i] if priorities else 0,
                              deadline_s=deadline_s, max_wall_s=max_wall_s))
    t0 = time.time()
    fin = eng.run()
    dt = time.time() - t0
    toks = {rid: np.asarray(fin[rid].tokens, np.int32) for rid in ids}
    return {
        "tokens": toks,
        "decode_s": dt,
        "tok_per_s": sum(len(t) for t in toks.values()) / dt,
        "stats": eng.stats(),
        "engine": eng,
    }


def init_params(key, cfg, mesh=None) -> dict:
    """Random weights from `key`, made on the device(s) that will hold
    them: with a mesh, each leaf is created already sharded by the
    serving rules (launch/sharding.py, experts over "model"), so a model
    that only fits across chips never lands whole on chip 0."""
    if mesh is None:
        return jax.jit(model_init, static_argnums=1)(key, cfg)
    from repro.launch.sharding import param_shardings
    shapes = jax.eval_shape(lambda k: model_init(k, cfg), key)
    out = param_shardings(shapes, cfg, mesh, mode="serve")
    return jax.jit(model_init, static_argnums=1, out_shardings=out)(key, cfg)


def _supervise(args) -> None:
    """The --supervise parent: re-exec this CLI (minus --supervise) as a
    watched child. The child journals; a restarted generation finds the
    committed snapshot in --journal-dir and recovers instead of starting
    fresh. The parent only watches, so it never initialises a JAX backend:
    a parent that did would hold the accelerator its child needs."""
    from repro.runtime.fault import ProcessSupervisor
    os.makedirs(args.journal_dir, exist_ok=True)
    child = [sys.executable, "-m", "repro.launch.serve"] + \
        [a for a in sys.argv[1:] if a != "--supervise"]
    sup = ProcessSupervisor(
        child, heartbeat_file=os.path.join(args.journal_dir, "heartbeat"))
    code = sup.run()
    print(f"supervised serve exited {code} after "
          f"{sup.stats.restarts} restart(s), "
          f"{sup.stats.heartbeat_kills} heartbeat kill(s) "
          f"(exit codes {sup.stats.exit_codes})")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--static", action="store_true",
                    help="static-batch generate() instead of the engine")
    ap.add_argument("--batch", type=int, default=4,
                    help="batch size for --static")
    ap.add_argument("--requests", type=int, default=8,
                    help="request count for the engine")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--backend", choices=["auto", "xla", "pallas"],
                    default=None,
                    help="MoE execution backend override (default: config)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy, the default)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (with --temperature > 0)")
    ap.add_argument("--buckets", action="store_true",
                    help="pad prompts to power-of-two buckets (one prefill "
                         "compile per bucket instead of per length)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV pool: block-table pages instead of dense "
                         "per-slot rows (attention-family archs)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (with --paged)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="page-pool size incl. the null page (0 = match the "
                         "dense pool's token capacity); smaller values "
                         "simulate a tighter HBM budget")
    ap.add_argument("--kv-quant", choices=["none", "int8"], default="none",
                    help="quantized decode state: int8 KV pages + GO rows "
                         "with per-page / per-row f32 scales (needs --paged; "
                         "~4x more pages per HBM byte, decode logits within "
                         "a small dequant bound of fp32)")
    ap.add_argument("--prefix-share", action="store_true",
                    help="copy-on-write prefix page sharing: prompts with a "
                         "page-aligned shared prefix map the same physical "
                         "pages and skip the shared prefill (needs --paged; "
                         "like REPRO_PREFIX_SHARE=1)")
    ap.add_argument("--expert-aware", action="store_true",
                    help="expert-aware admission: order admissions by "
                         "routing overlap with the active batch (MoE archs; "
                         "like REPRO_EXPERT_AWARE=1)")
    ap.add_argument("--chunk-prefill", type=int, default=0,
                    help="admit prompts longer than this one chunk per tick "
                         "(0 = one-shot prefill); must divide max_tokens")
    ap.add_argument("--priority", type=int, default=0,
                    help="admission priority for the submitted requests "
                         "(lower = admitted first; FIFO within a level)")
    ap.add_argument("--preemption", action="store_true",
                    help="let blocked higher-priority admissions evict "
                         "lower-priority streams (paged pools; evicted "
                         "streams resume bit-identically)")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="per-request wall budget from submission "
                         "(0 = unbounded; exceeded -> status TIMEOUT)")
    ap.add_argument("--max-wall-s", type=float, default=0.0,
                    help="per-request wall budget from first admission "
                         "(0 = unbounded; exceeded -> status TIMEOUT)")
    ap.add_argument("--journal-dir", default="",
                    help="durable request journal + atomic engine snapshots "
                         "in this directory (needs --paged). If it already "
                         "holds a committed snapshot, the run RECOVERS from "
                         "it (replaying the journal tail, resuming every "
                         "live stream bit-identically) instead of starting "
                         "fresh")
    ap.add_argument("--snapshot-every", type=int, default=8,
                    help="engine ticks between atomic snapshots "
                         "(with --journal-dir)")
    ap.add_argument("--supervise", action="store_true",
                    help="run the engine in a supervised child process: "
                         "file-mtime heartbeats, missed-heartbeat SIGKILL, "
                         "exponential-backoff restart, each restarted "
                         "generation re-dispatches through recover() "
                         "(needs --journal-dir)")
    ap.add_argument("--crash-step", type=int, default=-1,
                    help="chaos: SIGKILL the engine process at this engine "
                         "tick, first generation only — restarted "
                         "generations run through (the kill-recover-resume "
                         "lane; needs --journal-dir)")
    ap.add_argument("--chaos", action="store_true",
                    help="seeded fault injection: transient tick failures, "
                         "admission pressure, forced preemptions "
                         "(serving/chaos.py; like REPRO_CHAOS=1)")
    ap.add_argument("--chaos-seed", type=int, default=0)
    ap.add_argument("--mesh-model", type=int, default=0,
                    help="run the engine under a smoke mesh with this "
                         "model-axis size (slot rows shard over the rest; "
                         "set XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N first on a single-device host)")
    args = ap.parse_args()
    if args.static and args.mesh_model:
        ap.error("--mesh-model shards the engine's slot pool; it has no "
                 "effect on the static generate() path (drop --static)")
    if args.journal_dir and not args.paged:
        ap.error("--journal-dir needs --paged (engine snapshots are "
                 "SlotPool.snapshot block-table surgery)")
    if args.kv_quant != "none" and not args.paged:
        ap.error("--kv-quant needs --paged (scale granularity IS page "
                 "granularity — there is nothing to quantize per-page "
                 "in the dense pool)")
    if (args.supervise or args.crash_step >= 0) and not args.journal_dir:
        ap.error("--supervise/--crash-step need --journal-dir (restarted "
                 "generations re-dispatch through recover())")

    if args.supervise:
        # before anything that could touch a device: the parent only watches
        _supervise(args)
        return

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    if args.backend is not None and cfg.moe is not None:
        import dataclasses
        cfg = cfg.with_overrides(
            moe=dataclasses.replace(cfg.moe, backend=args.backend))
    mesh = None
    if args.mesh_model:
        from repro.launch.mesh import make_smoke_mesh
        mesh = make_smoke_mesh(model=args.mesh_model)
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg, mesh)
    extras = {}
    if cfg.cross_attn_every:
        extras["image_embeds"] = extras["memory"] = jnp.zeros(
            (1 if not args.static else args.batch, cfg.num_image_tokens,
             cfg.d_model), jnp.dtype(cfg.dtype))

    if args.static:
        prompts = jax.random.randint(
            key, (args.batch, args.prompt), 0, cfg.vocab_size, dtype=jnp.int32)
        res = generate(params, cfg, prompts, args.gen, extras)
        print(f"generated {res['tokens'].shape} in {res['decode_s']:.2f}s "
              f"({res['tok_per_s']:.1f} tok/s)")
        print("sample:", np.asarray(res["tokens"][0])[:16])
        return

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=args.prompt, dtype=np.int32)
               for _ in range(args.requests)]
    # staggered arrivals: one new request every other engine tick
    arrivals = [2 * i for i in range(args.requests)]
    chaos = None
    if args.chaos:
        from repro.serving import Chaos
        chaos = Chaos(seed=args.chaos_seed, tick_fail=0.05, pressure=0.05,
                      preempt=0.05)
    if args.crash_step >= 0 and int(os.environ.get(
            "REPRO_SUPERVISE_GENERATION", "0") or 0) == 0:
        # arm the crash in the FIRST generation only: the restarted one
        # must sail past the same tick number to prove recovery terminates
        from repro.serving import Chaos
        if chaos is None:
            chaos = Chaos(seed=args.chaos_seed)
        chaos.crash_step = args.crash_step

    if args.journal_dir:
        from repro.serving import EngineJournal, ServingEngine
        if EngineJournal.recoverable(args.journal_dir):
            t0 = time.time()
            eng = ServingEngine.recover(args.journal_dir, params, cfg,
                                        mesh=mesh, chaos=chaos,
                                        snapshot_every=args.snapshot_every)
            fin = eng.run()
            dt = time.time() - t0
            info, s = eng.recovered_info, eng.stats()
            print(f"recovered from {args.journal_dir} (snapshot seq "
                  f"{info['snapshot_seq']}, {info['events']} replayed "
                  f"events, {info['wall_ms']:.1f}ms) — drained to "
                  f"{s['finished']} finished requests in {dt:.2f}s")
            print(f"statuses: {s['statuses']}  recoveries: "
                  f"{s['recoveries']}  restart generation: "
                  f"{s['restart_count']}")
            if fin:
                first = fin[min(fin)].tokens
                print("sample:", np.asarray(first[:16], np.int32))
            return

    res = serve_continuous(params, cfg, prompts, args.gen,
                           num_slots=args.slots, extras=extras or None,
                           arrival_steps=arrivals, mesh=mesh,
                           temperature=args.temperature, top_p=args.top_p,
                           prompt_buckets=args.buckets, paged=args.paged,
                           page_size=args.page_size,
                           num_pages=args.num_pages or None,
                           kv_quant=(args.kv_quant
                                     if args.kv_quant != "none" else None),
                           prefill_chunk=args.chunk_prefill,
                           priorities=[args.priority] * len(prompts),
                           preemption=args.preemption, chaos=chaos,
                           deadline_s=args.deadline_s or None,
                           max_wall_s=args.max_wall_s or None,
                           prefix_share=args.prefix_share or None,
                           expert_aware=args.expert_aware or None,
                           journal_dir=args.journal_dir or None,
                           snapshot_every=args.snapshot_every)
    s = res["stats"]
    print(f"served {s['finished']} requests over {s['steps']} ticks on "
          f"{args.slots} slots in {res['decode_s']:.2f}s "
          f"({res['tok_per_s']:.1f} tok/s)"
          + (f" [mesh {s['mesh']}]" if s["mesh"] else "")
          + (f" [paged ps={s['page_size']} pages={s['num_pages']}]"
             if s["paged"] else "")
          + (f" [kv-quant {s['kv_quant_dtype']} "
             f"{s['kv_bytes_per_token']:.0f} B/tok, dequant err "
             f"{s['dequant_max_abs_err']:.2e}]"
             if s["kv_quant_dtype"] else "")
          + (f" [chunk ticks {s['chunk_ticks']}]" if s["chunk_ticks"] else "")
          + (f" [prefix hits {s['prefix_hits']} shared pages "
             f"{s['pages_shared']} prefill skipped "
             f"{s['prefill_tokens_skipped']} tok]"
             if s["prefix_share"] else "")
          + (" [expert-aware]" if s["expert_aware"] else "")
          + (f" [journal {s['journal_bytes']}B, {s['snapshots']} snaps]"
             if s["journal_bytes"] else ""))
    print(f"statuses: {s['statuses']}  preemptions: {s['preemptions']} "
          f"(resumes {s['resumes']})  tick retries: {s['tick_retries']}"
          + (f"  chaos: {s['chaos']}" if s["chaos"] else ""))
    first = res["tokens"][min(res["tokens"])]
    print("sample:", first[:16])


if __name__ == "__main__":
    main()
