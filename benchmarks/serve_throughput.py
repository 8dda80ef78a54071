"""Continuous-batching serving throughput under a Poisson arrival trace.

Replays one open-loop trace (exponential inter-arrival times in engine
ticks, mixed generation lengths) against the ServingEngine at several slot
counts and reports, per slot count:

  tok_per_s       generated tokens / wall-clock of the whole trace
  p50_ms / p95_ms request latency (arrival -> final token), wall-clock
  steps           engine ticks to drain the trace
  max_concurrent  peak simultaneously-active slots (deterministic)

Two head-to-head sections ride along in the JSON report:

  paged_vs_dense   the same trace against a dense pool and a PAGED pool
                   holding the SAME simulated HBM budget (token capacity).
                   Dense spends budget/max_tokens slots; paged spends a
                   page per page_size tokens, so short requests pack — the
                   paged pool must sustain strictly MORE concurrent streams
                   (max_concurrent, deterministic, gated by
                   benchmarks/check_regression.py).
  chunked_prefill  a long-prompt trace served with one-shot vs chunked
                   prefill (prefill_chunk tokens/tick): a one-shot long
                   prefill stalls every in-flight stream for its full wall
                   time, so the p95 ENGINE-TICK latency (p95_tick_ms — the
                   inter-token stall a stream experiences) spikes to the
                   prefill cost; chunking bounds per-tick prefill work to
                   one chunk, collapsing that tail (wall-clock — archived,
                   not gated).
  paged_attn       the same paged trace under cfg.paged_attn="kernel" vs
                   "gather": analytic per-decode-tick HBM attention
                   traffic (deterministic — gated: kernel bytes strictly
                   below gather bytes, ratio must not regress, token
                   streams must match), plus archived wall clocks.
  preemption       a mixed-priority Poisson trace on a page-starved pool,
                   preemption ON (blocked high-priority admissions evict
                   the lowest-priority stream, which later resumes from
                   its snapshot) vs OFF (admission blocking). Gated,
                   deterministic: >= 1 preemption fires, every stream is
                   bit-identical across the two modes (eviction/resume is
                   invisible in the output), and the high-priority p95
                   turnaround in ENGINE TICKS under preemption stays
                   strictly below blocking. Wall clocks archived.
  prefix_sharing   the shared-system-prompt trace with prefix sharing OFF
                   vs ON: followers admit from the page-aligned prefix
                   cache (refcounted copy-on-write pages, cached prefill
                   logits) instead of re-prefilling. Gated, deterministic:
                   prefix_hits / pages_shared / prefill_tokens_skipped are
                   exact integers and the streams must be bit-identical.
  crash_recovery   kill–recover–resume on a journaled trace: the engine is
                   abandoned mid-decode at a fixed tick (the in-process
                   SIGKILL analogue), ServingEngine.recover restores the
                   latest committed snapshot and replays the journal tail,
                   and the drained streams must be bit-identical to an
                   uninterrupted engine (gated, with the replayed-event and
                   restored-stream counts exact; recovery wall-ms archived).
  expert_balance   an alternating two-routing-class workload under FIFO vs
                   expert-aware admission: the mean experts touched per
                   decode tick (reconstructed from the deterministic
                   admit/finish windows — the planner's tiles-per-tick
                   objective) must drop strictly, streams bit-identical.

Compilation is excluded: each engine variant warms up prefill + its
pool-width decode step on a throwaway request before the timed run.

With --out the rows are also written as machine-readable JSON
(``BENCH_serve_throughput.json``); the deterministic occupancy fields are
CI-gated against the committed baseline, the wall-clock fields are
archived only.

  PYTHONPATH=src python -m benchmarks.serve_throughput --smoke
  PYTHONPATH=src python benchmarks/serve_throughput.py --smoke --slots 1,4,8
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np


def build_trace(rng, num_requests: int, prompt_len: int, gen: int,
                rate: float, vocab: int, long_every: int = 0,
                long_prompt_len: int = 0):
    """Open-loop Poisson trace: arrival tick, prompt, gen length per request.
    With `long_every` > 0, every long_every-th request carries a
    `long_prompt_len`-token prompt (the chunked-prefill stressor)."""
    gaps = rng.exponential(1.0 / rate, size=num_requests)
    arrivals = np.floor(np.cumsum(gaps)).astype(int)
    arrivals[0] = 0
    lens = [long_prompt_len if long_every and i % long_every == 0
            else prompt_len for i in range(num_requests)]
    prompts = [rng.integers(0, vocab, size=n, dtype=np.int32) for n in lens]
    gens = rng.integers(max(1, gen // 2), gen + 1, size=num_requests)
    return arrivals, prompts, gens


def run_trace(params, cfg, *, num_slots: int, max_tokens: int,
              arrivals, prompts, gens, **engine_kw) -> dict:
    from repro.serving import ServingEngine

    # warmup: compile prefill (every distinct prompt length in the trace)
    # + this pool width's decode step off the clock
    warm = ServingEngine(params, cfg, num_slots=num_slots,
                         max_tokens=max_tokens, **engine_kw)
    seen = set()
    for p in prompts:
        if len(p) not in seen:
            seen.add(len(p))
            warm.submit(p, 2)
    warm.run()

    eng = ServingEngine(params, cfg, num_slots=num_slots,
                        max_tokens=max_tokens, **engine_kw)
    ids = [eng.submit(p, int(g), arrival_step=int(a))
           for p, g, a in zip(prompts, gens, arrivals)]
    t0 = time.monotonic()
    ticks = []                 # wall time per busy engine tick (inter-token
    while eng.has_work():      # stall seen by streams)
        busy = eng.pool.any_active()
        before_chunks = eng.chunk_ticks
        tt = time.monotonic()
        eng.step()
        if busy or eng.chunk_ticks > before_chunks:
            ticks.append(time.monotonic() - tt)
    dt = time.monotonic() - t0
    fin = eng.finished

    lats = np.array([fin[i].latency_s for i in ids])
    toks = sum(len(fin[i].tokens) for i in ids)
    ticks = np.array(ticks) if ticks else np.zeros(1)
    row = {
        "slots": num_slots,
        "tok_per_s": toks / dt,
        "p50_ms": float(np.percentile(lats, 50) * 1e3),
        "p95_ms": float(np.percentile(lats, 95) * 1e3),
        "p95_tick_ms": float(np.percentile(ticks, 95) * 1e3),
        "max_tick_ms": float(ticks.max() * 1e3),
        "steps": eng.step_count,
        "wall_s": dt,
        "tokens": toks,
        # engine-tracked peak occupancy (after admissions, before same-tick
        # retirements — the true concurrent-stream count; deterministic)
        "max_concurrent": eng.peak_active,
    }
    if eng.pool.paged:
        row["num_pages"] = eng.pool.num_pages
        row["page_size"] = eng.pool.page_size
    if eng.chunk_ticks:
        row["chunk_ticks"] = eng.chunk_ticks
    return row


def paged_vs_dense(params, cfg, rng, *, budget_tokens: int, max_tokens: int,
                   page_size: int, num_requests: int, prompt_len: int,
                   gen: int, rate: float) -> dict:
    """Same trace, same simulated HBM token budget: dense carves the budget
    into budget/max_tokens fixed slots; paged carves it into pages and lets
    the allocator pack short requests. max_concurrent is deterministic
    (tick-based trace, length-based retirement)."""
    arrivals, prompts, gens = build_trace(
        rng, num_requests, prompt_len, gen, rate, cfg.vocab_size)
    dense_slots = max(1, budget_tokens // max_tokens)
    num_pages = budget_tokens // page_size + 1           # +1: the null page
    paged_slots = min(3 * dense_slots,
                      budget_tokens // max(1, prompt_len + gen))
    trace_kw = dict(max_tokens=max_tokens, arrivals=arrivals,
                    prompts=prompts, gens=gens)
    dense = run_trace(params, cfg, num_slots=dense_slots, **trace_kw)
    paged = run_trace(params, cfg, num_slots=paged_slots, paged=True,
                      page_size=page_size, num_pages=num_pages, **trace_kw)
    return {
        "budget_tokens": budget_tokens,
        "max_tokens": max_tokens,
        "page_size": page_size,
        "trace": {"requests": num_requests, "prompt_len": prompt_len,
                  "gen": gen, "rate": rate},
        "dense": dense,
        "paged": paged,
    }


def chunked_prefill_compare(params, cfg, rng, *, max_tokens: int,
                            chunk: int, num_requests: int, prompt_len: int,
                            long_prompt_len: int, gen: int, rate: float,
                            num_slots: int) -> dict:
    """Long-prompt Poisson trace served one-shot vs chunked: the chunked
    engine bounds per-tick prefill work to `chunk` tokens, so in-flight
    decodes never wait a full long prefill between tokens — the p95
    engine-tick (inter-token) latency collapses from the one-shot prefill
    cost down to roughly one chunk of work."""
    arrivals, prompts, gens = build_trace(
        rng, num_requests, prompt_len, gen, rate, cfg.vocab_size,
        long_every=3, long_prompt_len=long_prompt_len)
    trace_kw = dict(num_slots=num_slots, max_tokens=max_tokens,
                    arrivals=arrivals, prompts=prompts, gens=gens)
    one_shot = run_trace(params, cfg, **trace_kw)
    chunked = run_trace(params, cfg, prefill_chunk=chunk, **trace_kw)
    return {
        "chunk": chunk,
        "trace": {"requests": num_requests, "prompt_len": prompt_len,
                  "long_prompt_len": long_prompt_len, "long_every": 3,
                  "gen": gen, "rate": rate, "slots": num_slots},
        "one_shot": one_shot,
        "chunked": chunked,
    }


def paged_attn_compare(params, cfg, rng, *, num_slots: int, max_tokens: int,
                       page_size: int, num_requests: int, prompt_len: int,
                       gen: int, rate: float) -> dict:
    """Per-tick HBM attention traffic on one paged trace, kernel vs gather.

    The gather path re-materializes EVERY slot's full block table each
    decode tick, so its attention traffic scales with num_slots x
    max_tokens regardless of how short the live sequences are. The Pallas
    kernel (kernels/paged_attn.py) walks the block table and stages only
    each active row's live pages — floor(t/ps)+1 — so traffic scales with
    the live token count. Both byte counts are ANALYTIC
    (paged_attn.decode_tick_pages over the deterministic tick schedule:
    tick-based trace, length-based retirement — bit-identical across
    hosts) and CI-gated; the wall clocks of the two engine runs are
    archived only (the kernel runs in interpret mode off-TPU). The two
    engines' token streams must agree exactly — also gated."""
    from repro.kernels.paged_attn import decode_tick_pages, page_bytes
    from repro.serving import ServingEngine

    arrivals, prompts, gens = build_trace(
        rng, num_requests, prompt_len, gen, rate, cfg.vocab_size)
    pages_per_slot = max_tokens // page_size
    num_pages = num_slots * pages_per_slot + 1        # +1: the null page

    def run_mode(mode: str):
        c = cfg.with_overrides(paged_attn=mode)
        kw = dict(num_slots=num_slots, max_tokens=max_tokens, paged=True,
                  page_size=page_size, num_pages=num_pages)
        warm = ServingEngine(params, c, **kw)
        warm.submit(prompts[0], 2)
        warm.run()
        eng = ServingEngine(params, c, **kw)
        ids = [eng.submit(p, int(g), arrival_step=int(a))
               for p, g, a in zip(prompts, gens, arrivals)]
        live_pages = total_pages = decode_ticks = 0
        t0 = time.monotonic()
        while eng.has_work():
            if eng.pool.any_active():       # a decode step runs this tick
                decode_ticks += 1
                lp, tp = decode_tick_pages(
                    np.asarray(eng.pool.state["t"]), eng.pool.active_mask(),
                    page_size, num_slots, pages_per_slot)
                live_pages += lp
                total_pages += tp
            eng.step()
        dt = time.monotonic() - t0
        stream = tuple(tuple(int(t) for t in eng.finished[i].tokens)
                       for i in ids)
        return {"decode_ticks": decode_ticks, "live_pages": live_pages,
                "total_pages": total_pages, "wall_s": dt}, stream

    kernel, ks = run_mode("kernel")
    gather, gs = run_mode("gather")
    # the page tallies are a pure function of the tick schedule — both runs
    # must see the same one, or the modes scheduled differently
    for key in ("live_pages", "total_pages"):
        if kernel[key] != gather[key]:
            raise RuntimeError(
                f"paged_attn section, key {key!r}: kernel={kernel[key]} "
                f"gather={gather[key]} — the two modes diverged on the "
                "tick schedule, so the traffic ratio would be meaningless")
    pb = page_bytes(cfg, page_size)                   # per page, per layer
    hbm_kernel = kernel["live_pages"] * pb * cfg.num_layers
    hbm_gather = gather["total_pages"] * pb * cfg.num_layers
    return {
        "trace": {"requests": num_requests, "prompt_len": prompt_len,
                  "gen": gen, "rate": rate, "slots": num_slots},
        "max_tokens": max_tokens,
        "page_size": page_size,
        "page_kv_bytes_per_layer": pb,
        "hbm_kernel_bytes": int(hbm_kernel),
        "hbm_gather_bytes": int(hbm_gather),
        "traffic_ratio": hbm_kernel / hbm_gather,
        "streams_match": ks == gs,
        "kernel": kernel,
        "gather": gather,
    }


def preemption_compare(params, cfg, rng, *, num_slots: int, max_tokens: int,
                       page_size: int, num_pages: int, num_requests: int,
                       prompt_len: int, gen: int, rate: float,
                       hi_every: int) -> dict:
    """Mixed-priority Poisson trace on a page-starved pool: every
    `hi_every`-th request is priority 0 (interactive), the rest priority 5
    (batch). With the page budget sized for ~half the offered load, the
    high-priority class either EVICTS a batch stream (preemption on) or
    waits for pages like everyone else (admission blocking).

    Everything gated is deterministic (tick-based trace, length-based
    retirement, greedy decode): at least one preemption fires, the two
    modes produce bit-identical token streams for EVERY request (the
    snapshot/resume path is invisible in the output — the whole point),
    and the high-priority p95 turnaround in engine ticks (arrival ->
    finish) drops strictly below the blocking mode's. The price —
    extra ticks added to the evicted batch streams — is reported as
    `lo_turnaround_overhead_ticks` (archived, it is the knob's cost)."""
    from repro.serving import ServingEngine

    arrivals, prompts, gens = build_trace(
        rng, num_requests, prompt_len, gen, rate, cfg.vocab_size)
    prios = [0 if i % hi_every == hi_every - 1 else 5
             for i in range(num_requests)]

    def run_mode(preempt: bool):
        kw = dict(num_slots=num_slots, max_tokens=max_tokens, paged=True,
                  page_size=page_size, num_pages=num_pages,
                  preemption=preempt)
        warm = ServingEngine(params, cfg, **kw)
        warm.submit(prompts[0], 2)
        warm.run()
        eng = ServingEngine(params, cfg, **kw)
        ids = [eng.submit(p, int(g), arrival_step=int(a), priority=pr)
               for p, g, a, pr in zip(prompts, gens, arrivals, prios)]
        t0 = time.monotonic()
        fin = eng.run()
        dt = time.monotonic() - t0

        def turnaround(sel):
            return [fin[i].finish_step - fin[i].arrival_step
                    for i, pr in zip(ids, prios) if pr == sel]

        hi_t, lo_t = turnaround(0), turnaround(5)
        hi_lat = [fin[i].latency_s for i, pr in zip(ids, prios) if pr == 0]
        stream = tuple(tuple(int(t) for t in fin[i].tokens) for i in ids)
        return {
            "preemptions": eng.stats()["preemptions"],
            "resumes": eng.stats()["resumes"],
            "hi_p95_turnaround_ticks": int(np.percentile(hi_t, 95)),
            "hi_mean_turnaround_ticks": float(np.mean(hi_t)),
            "lo_mean_turnaround_ticks": float(np.mean(lo_t)),
            "hi_p95_ms": float(np.percentile(hi_lat, 95) * 1e3),
            "steps": eng.step_count,
            "wall_s": dt,
            "statuses": eng.stats()["statuses"],
        }, stream

    blocking, bs = run_mode(False)
    preempting, ps = run_mode(True)
    return {
        "trace": {"requests": num_requests, "prompt_len": prompt_len,
                  "gen": gen, "rate": rate, "slots": num_slots,
                  "hi_every": hi_every, "num_pages": num_pages,
                  "page_size": page_size},
        "streams_match": bs == ps,
        # what eviction costs the batch class (archived, not gated)
        "lo_turnaround_overhead_ticks":
            preempting["lo_mean_turnaround_ticks"]
            - blocking["lo_mean_turnaround_ticks"],
        "blocking": blocking,
        "preempt": preempting,
    }


def prefix_sharing_compare(params, cfg, rng, *, num_slots: int,
                           max_tokens: int, page_size: int,
                           num_requests: int, prompt_len: int,
                           gen: int) -> dict:
    """The shared-system-prompt workload: every request carries the SAME
    page-aligned prompt, arrivals staggered one per tick so the first
    admission's deposit is live before the rest look it up. With sharing
    OFF each admission pays a full prefill and private pages; with sharing
    ON the followers map the donor's pages copy-on-write and emit their
    first token from the cached prefill logits — zero prefill tokens.

    Everything gated is deterministic (tick-based trace, greedy decode):
    prefix_hits / pages_shared / prefill_tokens_skipped are exact integers,
    and the two modes' token streams must match bit for bit (sharing is
    correctness-neutral by construction). Wall clocks are archived only."""
    from repro.serving import ServingEngine

    prompt = rng.integers(0, cfg.vocab_size, size=prompt_len, dtype=np.int32)
    gens = rng.integers(max(1, gen // 2), gen + 1, size=num_requests)
    arrivals = np.arange(num_requests)

    def run_mode(share: bool):
        kw = dict(num_slots=num_slots, max_tokens=max_tokens, paged=True,
                  page_size=page_size, prefix_share=share)
        warm = ServingEngine(params, cfg, **kw)
        warm.submit(prompt, 2)
        warm.run()
        eng = ServingEngine(params, cfg, **kw)
        ids = [eng.submit(prompt, int(g), arrival_step=int(a))
               for g, a in zip(gens, arrivals)]
        t0 = time.monotonic()
        fin = eng.run()
        dt = time.monotonic() - t0
        st = eng.stats()
        stream = tuple(tuple(int(t) for t in fin[i].tokens) for i in ids)
        return {
            "prefix_hits": st["prefix_hits"],
            "pages_shared": st["pages_shared"],
            "prefill_tokens_skipped": st["prefill_tokens_skipped"],
            "steps": eng.step_count,
            "wall_s": dt,
            "statuses": st["statuses"],
        }, stream

    off, so = run_mode(False)
    on, sn = run_mode(True)
    return {
        "trace": {"requests": num_requests, "prompt_len": prompt_len,
                  "gen": gen, "slots": num_slots, "page_size": page_size},
        "streams_match": so == sn,
        "off": off,
        "on": on,
    }


def crash_recovery_compare(params, cfg, rng, *, num_slots: int,
                           max_tokens: int, page_size: int,
                           num_requests: int, prompt_len: int, gen: int,
                           rate: float, crash_step: int,
                           snapshot_every: int) -> dict:
    """Kill–recover–resume on a journaled trace: run the Poisson trace on a
    journaled engine, abandon it at `crash_step` ticks (the in-process
    SIGKILL analogue — everything durable is already fsync'd), recover from
    the journal directory, and drain.

    Gated and deterministic (tick-based trace, greedy decode): the
    recovered engine must finish EVERY stream bit-identical to an
    uninterrupted engine (streams_match), the crash point must actually
    leave live slots and journal-tail events to replay (recovered_streams,
    replayed_events — exact integers, no drift vs baseline). The recovery
    wall clock (restore + replay, before any decode tick) is archived as
    `recovery_wall_ms`, not gated."""
    import shutil
    import tempfile

    from repro.serving import ServingEngine

    arrivals, prompts, gens = build_trace(
        rng, num_requests, prompt_len, gen, rate, cfg.vocab_size)
    kw = dict(num_slots=num_slots, max_tokens=max_tokens, paged=True,
              page_size=page_size)

    warm = ServingEngine(params, cfg, **kw)
    warm.submit(prompts[0], 2)
    warm.run()

    ref_eng = ServingEngine(params, cfg, **kw)
    ids = [ref_eng.submit(p, int(g), arrival_step=int(a))
           for p, g, a in zip(prompts, gens, arrivals)]
    ref_fin = ref_eng.run()
    ref_stream = tuple(tuple(int(t) for t in ref_fin[i].tokens) for i in ids)

    jdir = tempfile.mkdtemp(prefix="repro_crash_bench_")
    try:
        eng = ServingEngine(params, cfg, journal_dir=jdir,
                            snapshot_every=snapshot_every, **kw)
        for p, g, a in zip(prompts, gens, arrivals):
            eng.submit(p, int(g), arrival_step=int(a))
        for _ in range(crash_step):
            eng.step()
        live_at_crash = eng.pool.num_active()

        t0 = time.monotonic()
        rec = ServingEngine.recover(jdir, params, cfg)
        recovery_wall_ms = (time.monotonic() - t0) * 1e3
        recovered_streams = rec.pool.num_active()
        fin = rec.run()
        stream = tuple(tuple(int(t) for t in fin[i].tokens) for i in ids)
        return {
            "trace": {"requests": num_requests, "prompt_len": prompt_len,
                      "gen": gen, "rate": rate, "slots": num_slots,
                      "page_size": page_size},
            "crash_step": crash_step,
            "snapshot_every": snapshot_every,
            "live_at_crash": live_at_crash,
            "recovered_streams": recovered_streams,
            "replayed_events": rec.replayed_events,
            "snapshot_seq": rec.recovered_info["snapshot_seq"],
            "recovery_wall_ms": recovery_wall_ms,       # archived, not gated
            "journal_bytes": rec.stats()["journal_bytes"],
            "streams_match": stream == ref_stream,
            "statuses": rec.stats()["statuses"],
        }
    finally:
        shutil.rmtree(jdir, ignore_errors=True)


def kv_quant_compare(params, cfg, rng, *, num_slots: int, max_tokens: int,
                     page_size: int, budget_fp32_pages: int,
                     num_requests: int, prompt_len: int, gen: int,
                     rate: float) -> dict:
    """Same Poisson trace, same simulated HBM BYTE budget, fp32 vs int8
    pages: the fp32 paged pool spends the budget on `budget_fp32_pages`
    pages; int8 pages (values + per-page per-kv-head scales) cost ~4x
    fewer bytes, so the same budget buys ~4x the pages and the allocator
    admits more concurrent streams.

    Gated and deterministic (tick-based trace, length-based retirement,
    greedy decode): the int8 engine must sustain >= 1.8x the fp32 engine's
    max_concurrent OR the analytic resident-KV bytes per token must drop
    to <= 0.55x (kv_bytes_per_token — both pure functions of the config),
    and a rerun of the int8 trace must be bit-identical (quantized decode
    is deterministic). The int8-vs-fp32 token agreement and the observed
    dequant round-trip error are ARCHIVED, not gated: quantized logits sit
    a bounded distance from fp32, which legitimately flips near-tied
    greedy argmaxes."""
    from repro.core.quant import kv_bytes_per_token
    from repro.kernels.paged_attn import page_bytes
    from repro.serving import ServingEngine

    arrivals, prompts, gens = build_trace(
        rng, num_requests, prompt_len, gen, rate, cfg.vocab_size)
    qcfg = cfg.with_overrides(kv_quant="int8")
    pb_fp32 = page_bytes(cfg, page_size) * cfg.num_layers
    pb_int8 = page_bytes(qcfg, page_size) * cfg.num_layers
    budget_bytes = budget_fp32_pages * pb_fp32
    int8_pages = int(budget_bytes // pb_int8)

    def run_mode(kv_quant, usable_pages):
        kw = dict(num_slots=num_slots, max_tokens=max_tokens, paged=True,
                  page_size=page_size, num_pages=usable_pages + 1,  # + null
                  kv_quant=kv_quant)
        warm = ServingEngine(params, cfg, **kw)
        warm.submit(prompts[0], 2)
        warm.run()
        eng = ServingEngine(params, cfg, **kw)
        ids = [eng.submit(p, int(g), arrival_step=int(a))
               for p, g, a in zip(prompts, gens, arrivals)]
        t0 = time.monotonic()
        fin = eng.run()
        dt = time.monotonic() - t0
        st = eng.stats()
        stream = tuple(tuple(int(t) for t in fin[i].tokens) for i in ids)
        return {
            "num_pages": usable_pages,
            "max_concurrent": eng.peak_active,
            "kv_quant_dtype": st["kv_quant_dtype"],
            # stats() reports it only for quantized pools; fp32 rows get
            # the same analytic figure so the ratio reads off the report
            "kv_bytes_per_token": st["kv_bytes_per_token"]
            or kv_bytes_per_token(eng.cfg, page_size),
            "dequant_max_abs_err": st["dequant_max_abs_err"],
            "steps": eng.step_count,
            "wall_s": dt,
            "statuses": st["statuses"],
        }, stream

    fp32, fs = run_mode("none", budget_fp32_pages)  # pin fp32 even if the
    # REPRO_KV_QUANT env lane is exported in this shell
    int8, qs = run_mode("int8", int8_pages)
    int8_rerun, qs2 = run_mode("int8", int8_pages)
    return {
        "trace": {"requests": num_requests, "prompt_len": prompt_len,
                  "gen": gen, "rate": rate, "slots": num_slots,
                  "page_size": page_size},
        "budget_bytes": int(budget_bytes),
        "page_bytes_fp32": int(pb_fp32),
        "page_bytes_int8": int(pb_int8),
        "stream_ratio": int8["max_concurrent"] / fp32["max_concurrent"],
        "bytes_per_token_ratio":
            kv_bytes_per_token(qcfg, page_size)
            / kv_bytes_per_token(cfg, page_size),
        "streams_deterministic": qs == qs2,
        "streams_match_fp32": qs == fs,       # archived — argmax flips OK
        "fp32": fp32,
        "int8": int8,
    }


def expert_balance_compare(params, cfg, rng, *, num_slots: int,
                           max_tokens: int, num_requests: int,
                           prompt_len: int, gen: int) -> dict:
    """Expert-aware admission vs FIFO on a two-class workload: scan the
    vocabulary for two repeated-token prompts whose layer-0 gate probes
    route to DISJOINT expert sets, then submit them alternating (worst case
    for FIFO — every tick's batch unions both classes' experts). The
    expert-aware scheduler groups same-class requests instead, so the mean
    experts-touched-per-decode-tick (reconstructed from the deterministic
    admit/finish tick windows against the probe signatures — the planner's
    tiles-per-tick objective) drops strictly below FIFO, while every stream
    stays bit-identical (admission order is correctness-neutral)."""
    from repro.serving import ServingEngine
    from repro.serving.engine import expert_signature

    base_sig = base_prompt = None
    pair = None
    for tok in range(min(cfg.vocab_size, 256)):
        p = np.full(prompt_len, tok, np.int32)
        sig = np.asarray(expert_signature(params, p, cfg), bool)
        if base_sig is None:
            base_sig, base_prompt = sig, p
        elif not (sig & base_sig).any():
            pair = [(base_prompt, base_sig), (p, sig)]
            break
    if pair is None:
        return {"skipped": "vocab scan found no disjoint expert signatures"}

    prompts = [pair[i % 2][0] for i in range(num_requests)]
    sigs = [pair[i % 2][1] for i in range(num_requests)]

    def run_mode(aware: bool):
        kw = dict(num_slots=num_slots, max_tokens=max_tokens,
                  expert_aware=aware)
        warm = ServingEngine(params, cfg, **kw)
        warm.submit(prompts[0], 2)
        warm.run()
        eng = ServingEngine(params, cfg, **kw)
        ids = [eng.submit(p, gen) for p in prompts]
        t0 = time.monotonic()
        fin = eng.run()
        dt = time.monotonic() - t0
        # experts the decode tick pays for = union of the active requests'
        # probe signatures, per tick (admit/finish steps are deterministic)
        per_tick = []
        for t in range(eng.step_count):
            union = np.zeros_like(sigs[0])
            n = 0
            for i, s in zip(ids, sigs):
                if fin[i].admit_step <= t < fin[i].finish_step:
                    union |= s
                    n += 1
            if n:
                per_tick.append(int(union.sum()))
        stream = tuple(tuple(int(t) for t in fin[i].tokens) for i in ids)
        return {
            "mean_experts_per_tick": float(np.mean(per_tick)),
            "steps": eng.step_count,
            "wall_s": dt,
            "statuses": eng.stats()["statuses"],
        }, stream

    fifo, sf = run_mode(False)
    aware, sa = run_mode(True)
    return {
        "trace": {"requests": num_requests, "prompt_len": prompt_len,
                  "gen": gen, "slots": num_slots,
                  "class_sizes": [int(pair[0][1].sum()),
                                  int(pair[1][1].sum())]},
        "streams_match": sf == sa,
        "fifo": fifo,
        "aware": aware,
    }


def run(arch: str = "llama_moe_4_16", smoke: bool = True,
        slot_counts=(1, 4, 8), num_requests: int = 8, prompt_len: int = 16,
        gen: int = 8, rate: float = 0.5, seed: int = 0,
        paged: bool = False, page_size: int = 16,
        compare: bool = True, out: str = "") -> dict:
    """Returns the full report dict ({"rows": [...per-slot-count...],
    "paged_vs_dense": ..., "chunked_prefill": ...}); with `out` it is also
    written as JSON."""
    import jax

    from repro.configs.registry import get_config
    from repro.models.model import model_init

    cfg = get_config(arch, smoke=smoke)
    params = model_init(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    arrivals, prompts, gens = build_trace(
        rng, num_requests, prompt_len, gen, rate, cfg.vocab_size)
    max_tokens = prompt_len + gen + 1
    kw = {}
    if paged:
        max_tokens += -max_tokens % page_size
        kw = dict(paged=True, page_size=page_size)

    rows = []
    for s in slot_counts:
        rows.append(run_trace(params, cfg, num_slots=s, max_tokens=max_tokens,
                              arrivals=arrivals, prompts=prompts, gens=gens,
                              **kw))
    report = {
        "host_backend": jax.default_backend(),
        "config": {"arch": arch, "smoke": smoke,
                   "requests": num_requests, "prompt_len": prompt_len,
                   "gen": gen, "rate": rate, "seed": seed, "paged": paged},
        "rows": rows,
    }
    if compare:
        # fixed-budget head-to-head: short requests against a generous
        # max_tokens, arrivals fast enough to saturate the pool
        report["paged_vs_dense"] = paged_vs_dense(
            params, cfg, np.random.default_rng(seed),
            budget_tokens=256 if smoke else 4096,
            max_tokens=64 if smoke else 256, page_size=16,
            num_requests=16 if smoke else 64,
            prompt_len=prompt_len, gen=gen, rate=2.0)
        report["chunked_prefill"] = chunked_prefill_compare(
            params, cfg, np.random.default_rng(seed),
            max_tokens=1024 if smoke else 2048, chunk=64,
            num_requests=9 if smoke else 33,
            prompt_len=8, long_prompt_len=960 if smoke else 1920,
            gen=gen, rate=0.7, num_slots=2 if smoke else 8)
        from repro.models.model import paged_supported
        if paged_supported(cfg):
            # tiny trace: off-TPU the kernel engine runs in interpret mode
            report["paged_attn"] = paged_attn_compare(
                params, cfg, np.random.default_rng(seed),
                num_slots=3, max_tokens=32 if smoke else 64, page_size=8,
                num_requests=6 if smoke else 12, prompt_len=8,
                gen=6, rate=1.0)
            # page-starved mixed-priority trace: pages for ~2 concurrent
            # streams, every 3rd request interactive (priority 0)
            report["preemption"] = preemption_compare(
                params, cfg, np.random.default_rng(seed),
                num_slots=3, max_tokens=16, page_size=8, num_pages=5,
                num_requests=9 if smoke else 24, prompt_len=8, gen=8,
                rate=0.4, hi_every=3)
            # shared-system-prompt trace: one donor prefill, the rest admit
            # from the prefix cache (page-aligned 16-token prompt, ps=8)
            report["prefix_sharing"] = prefix_sharing_compare(
                params, cfg, np.random.default_rng(seed),
                num_slots=4, max_tokens=32 if smoke else 64, page_size=8,
                num_requests=8 if smoke else 24, prompt_len=16, gen=8)
            # kill–recover–resume: crash mid-trace with slots live, recover
            # from the journal, drain — streams must match uninterrupted
            report["crash_recovery"] = crash_recovery_compare(
                params, cfg, np.random.default_rng(seed),
                num_slots=3, max_tokens=32 if smoke else 64, page_size=8,
                num_requests=6 if smoke else 16, prompt_len=8, gen=8,
                rate=1.0, crash_step=6, snapshot_every=4)
            # same simulated HBM byte budget, fp32 vs int8 pages: the byte
            # savings buy ~4x the pages, which admission turns into more
            # concurrent streams
            report["kv_quant"] = kv_quant_compare(
                params, cfg, np.random.default_rng(seed),
                num_slots=12, max_tokens=16, page_size=8,
                budget_fp32_pages=8,
                num_requests=16 if smoke else 48, prompt_len=8, gen=8,
                rate=2.0)
        else:
            report["paged_attn"] = {"skipped": "arch has no paged path"}
            report["preemption"] = {"skipped": "arch has no paged path"}
            report["prefix_sharing"] = {"skipped": "arch has no paged path"}
            report["crash_recovery"] = {"skipped": "arch has no paged path"}
            report["kv_quant"] = {"skipped": "arch has no paged path"}
        if cfg.moe is not None and cfg.block == "attn" \
                and cfg.encoder_layers == 0 and cfg.cross_attn_every == 0:
            # alternating two-class workload on a dense 2-slot pool (no
            # page confounds — this section isolates admission ORDER)
            report["expert_balance"] = expert_balance_compare(
                params, cfg, np.random.default_rng(seed),
                num_slots=2, max_tokens=32 if smoke else 64,
                num_requests=8 if smoke else 16, prompt_len=8, gen=8)
        else:
            report["expert_balance"] = {"skipped": "arch has no MoE gate"}
    if out:
        with open(out, "w") as f:
            json.dump(report, f, indent=2)
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama_moe_4_16")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--slots", default="1,4,8",
                    help="comma-separated slot counts")
    ap.add_argument("--requests", type=int, default=0,
                    help="0 -> 8 for --smoke, 32 otherwise")
    ap.add_argument("--prompt", type=int, default=0,
                    help="0 -> 16 for --smoke, 64 otherwise")
    ap.add_argument("--gen", type=int, default=0,
                    help="0 -> 8 for --smoke, 32 otherwise")
    ap.add_argument("--rate", type=float, default=0.5,
                    help="mean arrivals per engine tick")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--paged", action="store_true",
                    help="run the per-slot-count rows on the paged pool")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--no-compare", action="store_true",
                    help="skip the paged-vs-dense / chunked-prefill sections")
    ap.add_argument("--out", default="",
                    help="also write the rows as JSON to this path")
    args = ap.parse_args()

    slot_counts = [int(s) for s in args.slots.split(",")]
    n = args.requests or (8 if args.smoke else 32)
    p = args.prompt or (16 if args.smoke else 64)
    g = args.gen or (8 if args.smoke else 32)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    rep = run(args.arch, smoke=args.smoke, slot_counts=slot_counts,
              num_requests=n, prompt_len=p, gen=g, rate=args.rate,
              seed=args.seed, paged=args.paged, page_size=args.page_size,
              compare=not args.no_compare, out=args.out)
    print(f"# serve_throughput arch={args.arch} smoke={args.smoke} "
          f"requests={n} prompt={p} gen<={g} rate={args.rate} "
          f"paged={args.paged}")
    print("slots,tok_per_s,p50_ms,p95_ms,steps,wall_s,tokens,max_concurrent")
    for r in rep["rows"]:
        print(f"{r['slots']},{r['tok_per_s']:.1f},{r['p50_ms']:.0f},"
              f"{r['p95_ms']:.0f},{r['steps']},{r['wall_s']:.2f},"
              f"{r['tokens']},{r['max_concurrent']}")
    if not args.no_compare:
        pd = rep["paged_vs_dense"]
        print(f"# paged_vs_dense budget={pd['budget_tokens']}tok: dense "
              f"{pd['dense']['slots']} slots -> {pd['dense']['max_concurrent']}"
              f" streams ({pd['dense']['tok_per_s']:.1f} tok/s); paged "
              f"{pd['paged']['num_pages']} pages -> "
              f"{pd['paged']['max_concurrent']} streams "
              f"({pd['paged']['tok_per_s']:.1f} tok/s)")
        cp = rep["chunked_prefill"]
        print(f"# chunked_prefill chunk={cp['chunk']}: p95 inter-token "
              f"stall {cp['one_shot']['p95_tick_ms']:.0f}ms (one-shot, "
              f"max {cp['one_shot']['max_tick_ms']:.0f}ms) -> "
              f"{cp['chunked']['p95_tick_ms']:.0f}ms (chunked, max "
              f"{cp['chunked']['max_tick_ms']:.0f}ms)")
        pa = rep.get("paged_attn", {})
        if "skipped" not in pa:
            print(f"# paged_attn ps={pa['page_size']} "
                  f"max_tokens={pa['max_tokens']}: per-trace attention HBM "
                  f"{pa['hbm_kernel_bytes'] / 1e6:.2f}MB (kernel, live "
                  f"pages) vs {pa['hbm_gather_bytes'] / 1e6:.2f}MB (gather, "
                  f"every slot's full table) — ratio "
                  f"{pa['traffic_ratio']:.3f}, streams_match="
                  f"{pa['streams_match']}")
        px = rep.get("prefix_sharing", {})
        if "skipped" not in px:
            print(f"# prefix_sharing prompt={px['trace']['prompt_len']}tok "
                  f"x{px['trace']['requests']}: "
                  f"{px['on']['prefix_hits']} hits, "
                  f"{px['on']['pages_shared']} pages shared, "
                  f"{px['on']['prefill_tokens_skipped']} prefill tokens "
                  f"skipped (off: 0), streams_match={px['streams_match']}")
        eb = rep.get("expert_balance", {})
        if "skipped" not in eb:
            print(f"# expert_balance classes={eb['trace']['class_sizes']}: "
                  f"mean experts/tick "
                  f"{eb['fifo']['mean_experts_per_tick']:.2f} (fifo) -> "
                  f"{eb['aware']['mean_experts_per_tick']:.2f} "
                  f"(expert-aware), streams_match={eb['streams_match']}")
        cr = rep.get("crash_recovery", {})
        if "skipped" not in cr:
            print(f"# crash_recovery crash_step={cr['crash_step']}: "
                  f"{cr['recovered_streams']} live streams restored, "
                  f"{cr['replayed_events']} journal events replayed in "
                  f"{cr['recovery_wall_ms']:.1f}ms, streams_match="
                  f"{cr['streams_match']}")
        kq = rep.get("kv_quant", {})
        if "skipped" not in kq:
            print(f"# kv_quant budget={kq['budget_bytes'] / 1e6:.2f}MB: fp32 "
                  f"{kq['fp32']['num_pages']} pages -> "
                  f"{kq['fp32']['max_concurrent']} streams; int8 "
                  f"{kq['int8']['num_pages']} pages -> "
                  f"{kq['int8']['max_concurrent']} streams "
                  f"(x{kq['stream_ratio']:.2f}); bytes/token "
                  f"{kq['fp32']['kv_bytes_per_token']:.0f} -> "
                  f"{kq['int8']['kv_bytes_per_token']:.0f} "
                  f"(x{kq['bytes_per_token_ratio']:.3f}), "
                  f"dequant_err={kq['int8']['dequant_max_abs_err']:.2e}, "
                  f"deterministic={kq['streams_deterministic']}")
        pe = rep.get("preemption", {})
        if "skipped" not in pe:
            print(f"# preemption pages={pe['trace']['num_pages']}: hi-class "
                  f"p95 turnaround "
                  f"{pe['blocking']['hi_p95_turnaround_ticks']} ticks "
                  f"(blocking) -> "
                  f"{pe['preempt']['hi_p95_turnaround_ticks']} ticks "
                  f"({pe['preempt']['preemptions']} preemptions, lo-class "
                  f"overhead {pe['lo_turnaround_overhead_ticks']:+.1f} "
                  f"ticks), streams_match={pe['streams_match']}")


if __name__ == "__main__":
    main()
