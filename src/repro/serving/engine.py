"""Continuous-batching serving engine over the pooled KV + GO cache state.

The paper's GO cache makes each decode step O(1) per request; this engine
makes the REQUEST schedule dynamic too. One jitted decode step runs over a
fixed slot array with an active mask:

  admit    a queued request prefills into a free slot — its KV rows and
           per-layer GO cache entries are written in place (write_decode_slot)
           while the other slots keep decoding between engine ticks;
  decode   every tick advances ALL occupied slots one token in a single
           batched serve_step — slots sit at different positions thanks to
           the per-slot `t` vector, so nothing recompiles and nobody stalls;
  retire   a slot frees on EOS or length; its caches are reset
           (init_decode_slot) and the row is immediately reusable.

Greedy decoding is the default and is bit-identical per request to the
static-batch `repro.launch.serve.generate` path (tests/test_serving.py):
the same compiled kernels run in both, and every batched op is row-wise
independent. `submit(..., temperature=, top_p=, seed=)` switches a request
to temperature/top-p sampling — per-slot PRNG keys live in the pool, the
sampled step variant compiles only once a sampling request is active, and
greedy rows inside a sampling pool stay bit-identical.

PAGED POOL (`paged=True`): the per-slot KV rows become a shared page pool
with per-slot block tables (serving/pool.py + serving/paging.py). The
persistent KV residency is then bounded by `num_pages * page_size` tokens
instead of `num_slots * max_tokens` (the decode gather still materializes
a transient dense layout per layer — see the pool docstring), so a fixed
cache budget admits strictly more concurrent
streams whenever requests need less than max_tokens; admission asks the
allocator "pages reservable?" instead of only "slot free?". Greedy streams
stay bit-identical to the dense engine (the gathered pages reproduce the
dense layout exactly; pinned in tests/test_serving.py). Setting the
REPRO_FORCE_PAGED env var turns paging on for every engine whose config
supports it — the CI matrix uses it to run the whole serving suite paged.

CHUNKED PREFILL (`prefill_chunk=N` tokens): prompts longer than N are
admitted as page-granular chunks, one chunk per engine tick, interleaved
with the decode ticks of the in-flight slots — a long prompt no longer
stalls every stream for its full prefill. Dense archs stream identically to
one-shot prefill; expert-choice MoE routes each chunk at the CHUNK's
capacity and merges GO caches (go_cache_merge), so its streams are
deterministic per chunking but may differ from the one-shot engine's (the
prompt-bucketing caveat). At most one chunk run is in flight, and it holds
a claimed slot + reserved pages from the start, so completion can never
deadlock.

The MoE execution backend rides in through cfg.moe.backend: with "pallas"
the batched decode tick runs the selected-experts static-capacity decode
plan (~2*B*k/E rows per expert with an exact overflow fallback, instead of
B*E dense FFNs — kernels/ops.py:go_selected_ffn) and prefill flattens the
whole pool's FFN pairs into one packed tile plan. Streams stay
bit-identical to the static generate() path because both run the same
kernels (pinned with backend="pallas" in tests/test_serving.py).

With a `mesh`, the pool state is sharded by `launch/sharding.py` (slot rows
across the data-parallel replicas, KV sequence / GO expert dims over
"model"; paged pools shard the page dim over data-parallel and the page
interior over "model", block tables replicated) and every decode tick runs
inside the mesh context, so GSPMD partitions the batched step — including
the selected-experts grouped GEMM — across the replicas. Admission prefill
stays batch-1 (replicated) and is splatted into the sharded row; streams
remain bit-identical to the unsharded engine (pinned in
tests/test_moe_mesh.py).

FAULT DOMAIN: every request ends in a typed terminal status (Request.status
— DONE | TIMEOUT | CANCELLED | FAILED; see serving/scheduler.py). Requests
carry wall budgets (`deadline_s` from submit, `max_wall_s` from first
admission) checked at every tick; `cancel(rid)` retires a request wherever
it is (queued, mid-chunk-prefill, active, or parked preempted). With
`preemption=True` (paged pools only) a blocked higher-priority admission
EVICTS the lowest-priority active stream: its live KV pages + GO rows are
snapshotted host-side, its pages freed, and it resumes later via
block-table surgery into fresh pages — bit-identical to never evicting
(recompute-by-re-prefill is neither bit-exact for KV nor possible at all
for the expert-choice GO decode history; see SlotPool.snapshot). The jitted
decode tick runs under a StepSupervisor (runtime/fault.py — the training
loop's retry/telemetry pattern, same determinism argument), slots producing
non-finite logits are quarantined to FAILED without touching cohabiting
rows, and `REPRO_AUDIT=1` sweeps allocator + pool invariants every tick.
`serving/chaos.py` injects seeded faults into all of it (REPRO_CHAOS=1 is
the CI lane).
"""
from __future__ import annotations

import contextlib
import heapq
import itertools
import math
import os
import signal
import sys
import time
from collections import Counter
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant as Q
from repro.kernels.paged_attn import decode_tick_pages
from repro.models.model import (init_decode_state, page_stores,
                                paged_supported, prefill,
                                prefill_chunk as _model_prefill_chunk,
                                serve_step)
from repro.runtime.fault import StepSupervisor
from repro.runtime.trace import span
from repro.serving.chaos import Chaos
from repro.serving.journal import (EngineJournal, JournalError,
                                   request_from_record, request_record)
from repro.serving.paging import PrefixIndex
from repro.serving.pool import SlotPool
from repro.serving.scheduler import (ExpertAwareScheduler, FIFOScheduler,
                                     QueueFull, Request, RequestStatus,
                                     RequestTooLarge)

# chaos configs already seed-logged by THIS process — one reproducibility
# line per distinct config, not one per engine (benchmark sweeps build many)
_chaos_logged: set[str] = set()


@partial(jax.jit, static_argnames="cfg")
def _decode_step(params, state, tokens, active, cfg):
    """One batched decode tick. Retired slots still flow through the math
    (masking beats reshaping — shapes never change) but their position is
    pinned to 0 so they stay inside max_tokens until the next admission.
    Also returns per-row `ok` (all logits finite) — the engine quarantines
    rows that went non-finite without touching their cohabitants."""
    logits, state = serve_step(params, state, tokens, cfg)
    state["t"] = jnp.where(active, state["t"], 0)
    ok = jnp.isfinite(logits).all(axis=-1)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), state, ok


def _sample_tokens(logits, keys, temps, top_ps):
    """Per-row temperature/top-p sampling over [B, V] logits; rows with
    temperature <= 0 take the greedy argmax (bit-identical to the greedy
    engine). top_p keeps the smallest prefix of the probability-sorted
    vocabulary whose mass reaches top_p — as top_p -> 0 only the argmax
    survives, so sampling degenerates to greedy exactly."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def row(lg, key, temp, tp):
        lg = (lg / jnp.maximum(temp, 1e-6)).astype(jnp.float32)
        srt, idx = jax.lax.top_k(lg, lg.shape[-1])
        probs = jax.nn.softmax(srt)
        keep = (jnp.cumsum(probs) - probs) < tp     # first token always kept
        filt = jnp.where(keep, srt, -jnp.inf)
        return idx[jax.random.categorical(key, filt)].astype(jnp.int32)

    sampled = jax.vmap(row)(logits, keys, temps, top_ps)
    return jnp.where(temps > 0, sampled, greedy)


@partial(jax.jit, static_argnames="cfg")
def _decode_step_sampled(params, state, tokens, active, temps, top_ps, keys,
                         cfg):
    """Sampling variant of the decode tick: compiled only once at least one
    active request asks for temperature > 0, so pure-greedy serving never
    pays the per-row vocab sort."""
    logits, state = serve_step(params, state, tokens, cfg)
    state["t"] = jnp.where(active, state["t"], 0)
    ok = jnp.isfinite(logits).all(axis=-1)
    split = jax.vmap(jax.random.split)(keys)        # [B, 2, 2]
    tok = _sample_tokens(logits, split[:, 0], temps, top_ps)
    return tok, state, ok, split[:, 1]


# prefill compiles once per (prompt length, max_len) and is shared across
# engine instances — module-level so benchmark sweeps don't recompile it.
# With prompt bucketing the padded length is a power-of-two bucket and the
# true length rides in as a TRACED valid_len, so one compile per bucket.
_jit_prefill = jax.jit(prefill, static_argnames=("cfg", "max_len"))
# chunk start/valid_len are traced: ONE compile per chunk length serves
# every chunk of every prompt.
_jit_prefill_chunk = jax.jit(_model_prefill_chunk, static_argnames="cfg")


def _env_on(name: str) -> bool:
    return os.environ.get(name, "").strip().lower() not in \
        ("", "0", "false", "no")


# the submit-time gate probe examines at most this many leading prompt
# tokens, zero-padded to exactly this length: ONE probe compile per config
# instead of one per distinct prompt length (a per-length retrace would
# spike submit() latency on varied-length workloads)
_PROBE_TOKENS = 64


@partial(jax.jit, static_argnames="cfg")
def _gate_probe(params, tokens, valid, cfg):
    """Layer-0 router probe over raw prompt EMBEDDINGS: which experts would
    each token's top_k pick if the gate saw the embedding directly? A cheap
    [P, d] @ [d, E] over a FIXED [_PROBE_TOKENS] leading slice (pad rows
    masked out of the scatter) — no attention, no layers, one compile — so
    the scheduler can fingerprint a prompt at submit time. It is a
    HEURISTIC twice over (the real gate input is the post-attention hidden
    state, deeper layers route independently, and tokens past the probe
    window are unseen), which is fine: the signature only steers admission
    order, never any compute, so a wrong prediction costs batch composition
    quality, not correctness. Expert-choice archs refine it at admission
    from the actually-observed GO rows."""
    x = params["embed"][tokens].astype(jnp.float32)           # [P, d]
    gate = params["layers"]["moe"]["gate"][0]                 # layer 0 [d, E]
    _, idx = jax.lax.top_k(x @ gate.astype(jnp.float32), cfg.moe.top_k)
    # pad rows scatter to index E — out of range, dropped
    idx = jnp.where((jnp.arange(tokens.shape[0]) < valid)[:, None],
                    idx, cfg.moe.num_experts)
    return jnp.zeros((cfg.moe.num_experts,), bool).at[
        idx.reshape(-1)].set(True, mode="drop")


def expert_signature(params, prompt, cfg) -> np.ndarray:
    """Predicted expert footprint of a prompt: bool [num_experts], from its
    first _PROBE_TOKENS tokens."""
    prompt = np.asarray(prompt, np.int32).reshape(-1)[:_PROBE_TOKENS]
    valid = int(prompt.shape[0])
    if valid < _PROBE_TOKENS:
        prompt = np.pad(prompt, (0, _PROBE_TOKENS - valid))
    return np.asarray(_gate_probe(
        params, jnp.asarray(prompt, jnp.int32),
        jnp.asarray(valid, jnp.int32), cfg))


@dataclass
class TickRecord:
    """What one engine tick asked of the device, from values the engine
    already holds on the host (`engine.last_tick`). The same counters ride
    as arguments on the tick's `engine.step` span (`counters()`).
    Paged-attention pages are per layer — K/V pages, or a latent-attention
    model's latent pages: `live_pages` the decode rows' live pages,
    `grid_pages` the pages the decode kernel's grid covers, B x P,
    retired rows included (both 0 on a tick without decode or on a dense pool)."""
    step: int = 0                    # engine step count after the tick
    decode_rows: int = 0             # rows the decode program advanced
    chunks: list = field(default_factory=list)   # (start, valid) per run
    oneshot: list = field(default_factory=list)  # one-shot prompt lengths
    live_pages: int = 0
    grid_pages: int = 0

    def counters(self) -> dict:
        return {"decode_rows": self.decode_rows,
                "chunk_runs": len(self.chunks),
                "chunk_valid": sum(v for _, v in self.chunks),
                "oneshot_tokens": sum(self.oneshot),
                "live_pages": self.live_pages,
                "grid_pages": self.grid_pages}


@dataclass
class _ChunkJob:
    """One in-flight chunked prefill: a claimed slot, reserved pages, and a
    private batch-1 decode state that fills one chunk per tick. Dense pools
    carry private KV rows in `state`; paged pools instead carry the claimed
    block-table row (`page_row`) and thread the pool's page store through
    each chunk run — the prefill scatters straight into the pool's pages."""
    req: Request
    slot: int
    state: dict
    prompt: np.ndarray            # right-padded to a chunk multiple
    pos: int = 0                  # next chunk start
    logits: object = None         # last chunk's logits
    page_row: np.ndarray | None = None


class ServingEngine:
    """Continuous-batching engine: submit requests any time, run ticks."""

    def __init__(self, params, cfg, *, num_slots: int = 8,
                 max_tokens: int = 256, max_queue: int = 0,
                 extras: dict | None = None, mesh=None,
                 prompt_buckets: bool = False, paged: bool = False,
                 page_size: int = 16, num_pages: int | None = None,
                 kv_quant: str | None = None,
                 prefill_chunk: int = 0, preemption: bool = False,
                 chaos: Chaos | None = None,
                 prefix_share: bool | None = None,
                 expert_aware: bool | None = None,
                 journal_dir: str | bool | None = None,
                 snapshot_every: int = 0):
        self.params = params
        self.mesh = mesh
        force = _env_on("REPRO_FORCE_PAGED") or \
            _env_on("REPRO_FORCE_PAGED_KERNEL")
        if not paged and force and paged_supported(cfg):
            # CI knob: run any supporting engine paged. Snap the page size
            # to a common divisor of max_tokens (and prefill_chunk, when
            # chunking is on — chunks must stay page-granular) so arbitrary
            # test pools stay legal; if no usable divisor exists, leave the
            # engine dense rather than crash a config that is valid unforced.
            g = math.gcd(page_size, max_tokens)
            if prefill_chunk:
                g = math.gcd(g, prefill_chunk)
            if g >= 4:
                paged = True
                page_size = g
        # Paged-attention realization knobs resolve into cfg HERE, before
        # anything jit-keyed on cfg is built: cfg is the static compile key,
        # so env reads at trace time would silently split/miss caches.
        # REPRO_FORCE_PAGED_KERNEL is the CI lane (paged pool + Pallas
        # kernel everywhere); REPRO_PAGED_GATHER is the escape hatch back to
        # the dense-gather path and wins when both are set.
        if _env_on("REPRO_FORCE_PAGED_KERNEL") and paged_supported(cfg):
            cfg = cfg.with_overrides(paged_attn="kernel")
        if _env_on("REPRO_PAGED_GATHER"):
            cfg = cfg.with_overrides(paged_attn="gather")
        # Quantized decode state resolves into cfg the same way (cfg is the
        # static compile key). The REPRO_KV_QUANT env lane silently no-ops
        # where the pool won't be paged or the page geometry can't tile int8
        # pages; the explicit kwarg is an API contract — SlotPool raises a
        # typed error when it can't honor it. The latent pool of a
        # latent-attention model has no int8 form, prefix entries or journal
        # payload yet: the env lanes skip such an engine, the kwargs refuse.
        if kv_quant is None:
            if _env_on("REPRO_KV_QUANT") and paged and paged_supported(cfg) \
                    and page_size % 8 == 0 and not cfg.mla:
                cfg = cfg.with_overrides(kv_quant="int8")
        else:
            cfg = cfg.with_overrides(kv_quant=kv_quant)
        self.cfg = cfg
        self.pool = SlotPool(cfg, num_slots, max_tokens, extras, mesh=mesh,
                             paged=paged, page_size=page_size,
                             num_pages=num_pages)
        # --- prefix sharing / expert-aware admission knobs ---
        # resolved ONCE here (REPRO_FORCE_PAGED pattern): the env knobs are
        # semantics-preserving CI lanes, so they silently no-op on engines
        # whose shape can't support them; the explicit kwargs are API
        # contracts and raise instead.
        if prefix_share is None:
            prefix_share = _env_on("REPRO_PREFIX_SHARE") and self.pool.paged \
                and not cfg.mla
        elif prefix_share and not self.pool.paged:
            raise ValueError("prefix sharing needs a paged pool (it is "
                             "copy-on-write block-table surgery)")
        elif prefix_share and cfg.mla:
            raise ValueError("prefix sharing does not handle the latent page "
                             "pool of a latent-attention model yet")
        self.prefix_share = bool(prefix_share)
        # expert-aware admission needs observable routing: a plain-attention
        # MoE stack (the gate probe reads the stacked layer-0 gate)
        moe_ok = (cfg.moe is not None and cfg.block == "attn"
                  and cfg.encoder_layers == 0 and cfg.cross_attn_every == 0)
        if expert_aware is None:
            expert_aware = _env_on("REPRO_EXPERT_AWARE") and moe_ok
        elif expert_aware and not moe_ok:
            raise ValueError("expert-aware admission needs a plain-attention "
                             "MoE config (it scores routing overlap)")
        self.expert_aware = bool(expert_aware)
        self.scheduler = (
            ExpertAwareScheduler(num_slots, max_tokens, max_queue,
                                 num_experts=cfg.moe.num_experts)
            if self.expert_aware
            else FIFOScheduler(num_slots, max_tokens, max_queue))
        self.prefix_index = (
            PrefixIndex(self.pool.alloc, self.pool.page_size)
            if self.prefix_share else None)
        self.prefix_hits = 0
        self.pages_shared = 0
        self.prefill_tokens_skipped = 0
        self.step_count = 0
        self.finished: dict[int, Request] = {}
        # monotone id assignment that survives recovery (itertools.count
        # can't be snapshotted; a recycled id would collide in the journal)
        self._next_id = 0
        if prefill_chunk:
            if not paged_supported(cfg):
                raise ValueError("chunked prefill is attention-family only")
            if max_tokens % prefill_chunk:
                raise ValueError(
                    f"prefill_chunk={prefill_chunk} must divide "
                    f"max_tokens={max_tokens}")
            if paged and prefill_chunk % self.pool.page_size:
                raise ValueError(
                    f"prefill_chunk={prefill_chunk} must be page-granular "
                    f"(page_size={self.pool.page_size})")
        self.prefill_chunk = int(prefill_chunk)
        self._chunk_job: _ChunkJob | None = None
        self.chunk_ticks = 0
        # peak simultaneously-occupied engine capacity — occupied slots plus
        # the chunk-run lane — sampled at every admission and again after
        # the admission loop, BEFORE retirements. This is the
        # concurrent-stream count the paged-vs-dense benchmark gates on
        # (sampling after step() would miss streams that decode and retire,
        # or admit and instantly finish, on the same tick)
        self.peak_active = 0
        # pad prompts up to power-of-two buckets so prefill compiles once
        # per BUCKET instead of once per distinct prompt length (attention
        # families only — recurrent archs prefill step-by-step). Dense archs
        # reproduce the unbucketed streams exactly; MoE capacity constants
        # derive from the BUCKET length (ec_capacity(bucket) >
        # ec_capacity(true len)), so MoE streams are deterministic per
        # bucket but may differ from the unbucketed engine's.
        self.prompt_buckets = bool(
            prompt_buckets and cfg.block == "attn"
            and cfg.encoder_layers == 0 and cfg.cross_attn_every == 0)
        self.prefill_lengths: set[int] = set()
        # --- fault domain ---
        # explicit injector wins; otherwise the REPRO_CHAOS env lane
        self.chaos = chaos if chaos is not None else Chaos.from_env()
        if self.chaos is not None and self.chaos.preempt > 0 \
                and self.pool.paged:
            preemption = True      # forced evictions need the resume path
        if preemption and not self.pool.paged:
            raise ValueError("preemption needs a paged pool (eviction "
                             "snapshots are block-table surgery)")
        self.preemption = bool(preemption)
        # decode-tick supervisor: same determinism-makes-retry-safe argument
        # as the training loop's. max_retries must exceed the chaos
        # injector's max consecutive faults or the lane DoSes itself.
        self.supervisor = StepSupervisor(max_retries=3)
        # the counters of the newest tick (None before the first)
        self.last_tick: TickRecord | None = None
        self._tick = TickRecord()
        self._preempted: dict[int, dict] = {}   # rid -> eviction snapshot
        self.preempted_total = 0
        self.resumed_total = 0
        self.rejected_full = 0
        self.rejected_oversized = 0
        self.audit_every_tick = _env_on("REPRO_AUDIT")
        if self.chaos is not None:
            # one reproducibility line per distinct config: a chaos CI
            # failure must be replayable from the log alone
            desc = self.chaos.describe()
            if desc not in _chaos_logged:
                _chaos_logged.add(desc)
                print(f"[repro.serving] {desc}", file=sys.stderr)
        # --- durability (serving/journal.py) ---
        # journal_dir=False disables even the env pickup (recover() builds
        # its engine first and attaches the journal after replay); the
        # REPRO_JOURNAL_DIR env lane follows the REPRO_FORCE_PAGED pattern
        # (silently no-ops on engines journaling can't support), while the
        # explicit kwarg is an API contract and raises instead.
        self.journal: EngineJournal | None = None
        self.recoveries = 0
        self.replayed_events = 0
        self.recovered_info: dict | None = None
        self.restart_count = int(
            os.environ.get("REPRO_SUPERVISE_GENERATION", "0") or 0)
        self._replay_expect: dict[int, list[int]] = {}
        self._tick_toks: dict[int, int] = {}
        self._heartbeat = os.environ.get("REPRO_HEARTBEAT") or None
        self._engine_extras = extras
        self._engine_kw = dict(
            num_slots=num_slots, max_tokens=self.pool.max_tokens,
            max_queue=max_queue, paged=self.pool.paged,
            page_size=self.pool.page_size, num_pages=self.pool.num_pages,
            kv_quant=self.cfg.kv_quant,
            prefill_chunk=self.prefill_chunk, preemption=self.preemption,
            prompt_buckets=self.prompt_buckets,
            prefix_share=self.prefix_share, expert_aware=self.expert_aware)
        if journal_dir is None and journal_dir is not False:
            env_dir = os.environ.get("REPRO_JOURNAL_DIR", "").strip()
            if env_dir and self.pool.paged and extras is None \
                    and not cfg.mla:
                # unique per engine: one journal describes ONE engine's
                # lifecycle (sweeps build many engines per process)
                journal_dir = os.path.join(
                    env_dir, f"engine_{os.getpid()}_{id(self):x}")
        if isinstance(journal_dir, str):
            self._attach_journal(journal_dir, snapshot_every)

    # ------------------------------------------------------------- submission

    def submit(self, prompt, max_new_tokens: int, *, eos_id: int | None = None,
               extras: dict | None = None, arrival_step: int = 0,
               request_id: int | None = None, temperature: float = 0.0,
               top_p: float = 1.0, seed: int | None = None,
               priority: int = 0, deadline_s: float | None = None,
               max_wall_s: float | None = None) -> int:
        """Queue a request. `arrival_step` > current step defers arrival to
        that engine tick (trace replay). `temperature` > 0 switches the
        request's rows to temperature/top-p sampling (greedy rows in the
        same pool stay bit-identical). `priority` orders admission (lower =
        earlier; FIFO within a level). `deadline_s`/`max_wall_s` bound the
        request's wall clock from submission / first admission — exceeded
        budgets retire it with status TIMEOUT. Raises RequestTooLarge for a
        request that could never fit the pool and QueueFull (carrying the
        backlog depth) at max_queue — both counted in stats()["rejected"].
        Returns the request id."""
        if self.journal is not None and extras is not None:
            raise ValueError(
                "journaled engines reject per-request extras: cross-attn "
                "memory is neither journaled nor snapshotted, so a "
                "recovered re-prefill could not reproduce the stream")
        rid = request_id if request_id is not None else self._next_id
        self._next_id = max(self._next_id, rid + 1)
        req = Request(
            request_id=rid,
            prompt=np.asarray(prompt, np.int32).reshape(-1),
            max_new_tokens=int(max_new_tokens),
            eos_id=eos_id,
            extras=extras,
            arrival_step=arrival_step,
            priority=int(priority),
            temperature=float(temperature),
            top_p=float(top_p),
            seed=seed,
            deadline_s=deadline_s,
            max_wall_s=max_wall_s,
        )
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if not (0.0 < req.top_p <= 1.0):
            raise ValueError("top_p must be in (0, 1]")
        if self.pool.paged:
            # the paged analogue of the max_tokens check: a request whose
            # worst case exceeds the whole page pool could NEVER reserve,
            # so admission would stall the queue forever
            need = self.pool.pages_needed(req)
            usable = self.pool.num_pages - 1          # page 0 is the null page
            if need > usable:
                self.rejected_oversized += 1
                raise RequestTooLarge(
                    f"request {rid}: prompt({req.prompt_len}) + "
                    f"max_new_tokens({req.max_new_tokens}) needs {need} "
                    f"pages of {self.pool.page_size} tokens, but the pool "
                    f"only has {usable} usable pages")
        if self.expert_aware:
            with self._mesh_scope():
                req.expert_sig = expert_signature(
                    self.params, req.prompt, self.cfg)
        req.arrival_time = req.submit_time = time.monotonic()
        try:
            self.scheduler.submit(req, now_step=self.step_count)
        except QueueFull:
            self.rejected_full += 1
            raise
        except RequestTooLarge:
            self.rejected_oversized += 1
            raise
        if self.journal is not None:
            # journaled AFTER scheduler acceptance: a rejected request has
            # no lifecycle to recover
            self.journal.append("submit", req=request_record(req))
        return rid

    def cancel(self, rid: int) -> bool:
        """Retire request `rid` wherever it is — queued (or trace-pending),
        parked preempted, mid-chunk-prefill, or actively decoding — freeing
        its slot/pages and marking it CANCELLED (partial tokens kept in
        Request.tokens). Returns False if the id is unknown or already
        terminal."""
        if rid in self.finished:
            return False
        done: list[Request] = []
        req = self.scheduler.remove(rid)
        if req is not None:
            self._preempted.pop(rid, None)
            self._mark_finished(req, RequestStatus.CANCELLED, done,
                                reason="cancelled")
            return True
        job = self._chunk_job
        if job is not None and job.req.request_id == rid:
            self.pool.release_pages(rid)   # claimed chunk pages + reservation
            self._chunk_job = None
            self._mark_finished(job.req, RequestStatus.CANCELLED, done,
                                reason="cancelled")
            return True
        for slot, owner in enumerate(self.pool.owner):
            if owner is not None and owner.request_id == rid:
                self._retire_slot(slot, RequestStatus.CANCELLED, done,
                                  reason="cancelled")
                return True
        return False

    # ------------------------------------------------------------------ ticks

    def step(self) -> list[Request]:
        """One engine tick: expire blown deadlines, advance the
        chunked-prefill job (if any) by one chunk, admit due+queued requests
        into free slots (evicting lower-priority streams under page
        pressure when preemption is on), then advance every occupied slot
        one token under the tick supervisor. Returns requests finished on
        this tick.

        Each phase is a host span (runtime/trace.py) inside `engine.step`:
        `engine.expire`, `engine.chunk`, `engine.admit`,
        `engine.decode.dispatch` (holding `engine.decode.wait`),
        `engine.commit` and `engine.journal`; `engine.prefill.wait` sits in
        the phase that installs a prefilled request. The tick's counters
        (`TickRecord`) end up in `last_tick` and on the `engine.step`
        span."""
        done: list[Request] = []
        self._tick = tick = TickRecord()
        with span("engine.step") as step_span:
            self._step_phases(done, tick)
            tick.step = self.step_count
            step_span.set_metadata(**tick.counters())
        self.last_tick = tick
        return done

    def _step_phases(self, done: list[Request], tick: TickRecord) -> None:
        with span("engine.expire"):
            self._expire(time.monotonic(), done)

        for req in self.scheduler.poll(self.step_count):
            req.arrival_time = time.monotonic()

        if self._chunk_job is not None:
            with span("engine.chunk"):
                self._advance_chunk_job(done)

        # admission loop; a chaos pressure event skips it for one tick
        # (delays admissions without reordering them)
        if self.chaos is None or not self.chaos.pressure_event():
            with span("engine.admit"):
                self._admission_loop(done)

        self._note_occupancy()

        if self.chaos is not None:
            self._inject_state_faults()

        if self.pool.any_active():
            with span("engine.decode.dispatch"):
                self.pool.grow_active()
                active = self.pool.active_mask()
                tick.decode_rows = int(active.sum())
                if self.pool.paged:
                    tick.live_pages, tick.grid_pages = decode_tick_pages(
                        self.pool.t_host, active, self.pool.page_size,
                        self.pool.num_slots, self.pool.block_table.shape[1])
                toks, state, ok, new_keys = self._supervised_decode()
            with span("engine.commit"):
                self._commit_decode(toks, state, ok, new_keys, done)
        elif self._chunk_job is not None:
            self.step_count += 1              # prefill-only tick
        else:
            # idle tick — jump straight to the next trace arrival
            nxt = self.scheduler.next_arrival_step()
            self.step_count = max(self.step_count + 1,
                                  nxt if nxt is not None else 0)

        if self.journal is not None:
            with span("engine.journal"):
                if self._tick_toks:
                    # ONE durable record per decode tick — the token
                    # watermark every recovered stream is prefix-asserted
                    # against
                    self.journal.append("tick", step=self.step_count,
                                        toks=dict(self._tick_toks))
                    self._tick_toks.clear()
                if self.step_count - self.journal.last_snapshot_step >= \
                        self.journal.snapshot_every:
                    self.journal.commit_snapshot(self._snapshot_payload(),
                                                 self.step_count)
        if self._heartbeat:
            # liveness signal for the process supervisor (mtime staleness)
            with open(self._heartbeat, "a"):
                os.utime(self._heartbeat, None)
        self._maybe_crash()

        if self.audit_every_tick:
            self._audit()

    def _admission_loop(self, done: list[Request]) -> None:
        """Admit queued requests into free slots until the head blocks
        (preempting for it where preemption is on). A request leaves the
        queue (`Request.start_time`) when its slot is claimed for a
        one-shot prefill or its chunked prefill starts."""
        while True:
            free = self.pool.free_slots()
            if self._chunk_job is not None and \
                    self._chunk_job.slot in free:
                free.remove(self._chunk_job.slot)
            busy = self.pool.num_active() + \
                (1 if self._chunk_job is not None else 0)
            if self.expert_aware:
                # refresh the cost model's view of the active batch —
                # each admission changes it, so re-note every iteration
                self.scheduler.note_active(
                    [o.expert_sig for o in self.pool.owner
                     if o is not None])
            req = self.scheduler.next_admission(
                busy, can_admit=self._can_admit)
            if req is None:
                # blocked head + preemption on: evict a lower-priority
                # active stream and retry the admission
                if self.preemption and self._preempt_for_head():
                    continue
                return
            if req.request_id in self._preempted:
                self._resume(free[0], req)
                continue
            req.start_time = time.monotonic()
            if self.prefill_chunk and \
                    req.prompt_len > self.prefill_chunk and \
                    self._full_hit(req) is None and \
                    self._ext_hit(req) is None:
                # long prompt with no cached prefix: chunked prefill.
                # A prefix hit skips (part of) the prefill, so it takes
                # the synchronous admission path below instead of
                # queueing behind the single chunk lane.
                self._start_chunk_job(free[0], req)
            else:
                self._admit_any(free[0], req, done)

    def _commit_decode(self, toks, state, ok, new_keys,
                       done: list[Request]) -> None:
        """Commit a finished decode tick: the new pool state and sampling
        keys, then each row's token, finishes and quarantines."""
        self.pool.state = self.pool._pin(state)
        if new_keys is not None:
            # keys advance only after the tick COMMITS — a supervisor
            # retry must re-run with the same keys or sampled streams
            # would silently fork
            self.pool.keys = np.array(new_keys, dtype=np.uint32)
        self.pool.note_decoded()
        toks = np.asarray(toks)
        ok = np.asarray(ok)
        self.step_count += 1
        for slot, req in enumerate(self.pool.owner):
            if req is None:
                continue
            if not ok[slot]:
                # quarantine: this row's logits went non-finite; retire
                # it FAILED (no garbage token appended) — cohabiting
                # rows are untouched (every batched op is row-wise
                # independent)
                self._retire_slot(slot, RequestStatus.FAILED, done,
                                  reason="non-finite logits")
                continue
            tok = int(toks[slot])
            req.tokens.append(tok)
            self._journal_token(req, tok)
            self.pool.pending[slot] = tok
            self.pool.remaining[slot] -= 1
            if self.pool.remaining[slot] <= 0 or \
                    (req.eos_id is not None and tok == req.eos_id):
                self._finish(slot, done)

    def has_work(self) -> bool:
        """Anything left to do — queued/deferred requests, occupied slots,
        or an in-flight chunked prefill. The run() drain condition, public
        so external tick loops (benchmarks) stay in sync with it."""
        return self.scheduler.has_pending() or self.pool.any_active() \
            or self._chunk_job is not None

    def run(self) -> dict[int, Request]:
        """Tick until queue, trace, chunk run and pool drain; returns
        finished requests keyed by request id (token streams in
        Request.tokens). Draining also flushes the prefix index: run() means
        "this workload is over", so the cache's page pins are dropped and a
        fully-retired pool again holds zero pages (open-ended tick loops —
        `while has_work(): step()` — keep the cache warm across requests,
        which is where prefix sharing actually pays)."""
        while self.has_work():
            self.step()
        if self.prefix_index is not None:
            self.pool.scrub_released(self.prefix_index.flush())
        return self.finished

    # -------------------------------------------------------------- internals

    def _note_occupancy(self) -> None:
        """Record peak engine occupancy: occupied slots + the in-flight
        chunk run (it holds a claimed slot and reserved pages)."""
        self.peak_active = max(
            self.peak_active,
            self.pool.num_active() + (1 if self._chunk_job is not None else 0))

    def _can_admit(self, req: Request) -> bool:
        """Admission gate with page-pressure cache reclaim: the prefix
        index's node pins are OPPORTUNISTIC, a blocked admission is not —
        if the head doesn't fit, evict LRU prefix-cache entries (scrubbing
        the freed pages) until it does or the cache is dry. Reclaim happens
        before live-stream preemption ever gets consulted, and is gated on
        `pool.can_admit` so a chunk-lane wait (not page pressure) never
        drains the cache."""
        ok = self._can_admit_now(req)
        while not ok and self.prefix_index is not None \
                and len(self.prefix_index) and not self.pool.can_admit(req):
            self.pool.scrub_released(self.prefix_index.reclaim_one())
            ok = self._can_admit_now(req)
        return ok

    def _can_admit_now(self, req: Request) -> bool:
        """One admission-gate evaluation: pages must be reservable (paged
        pool), and a to-be-chunked prompt must wait for the single
        chunk-run lane. A blocked head blocks the queue — overtaking would
        break the starvation-freedom the priority heap guarantees. A
        PREEMPTED head resumes from its snapshot: it needs only its
        remaining worst case and never re-prefills, so the chunk lane is
        irrelevant to it. A prefix-index hit discounts the shared pages
        from the gate — copy-on-write references consume nothing from the
        free list, so an admission the cache mostly covers squeezes in
        where a cold one couldn't."""
        if req.request_id in self._preempted:
            return self.pool.can_resume(self._preempted[req.request_id])
        if self.prefix_share:
            entry = self._full_hit(req)
            if entry is not None:
                return self.pool.alloc.can_reserve(
                    self.pool.pages_needed(req) - len(entry["nodes"]))
            shared = self._ext_hit(req)
            if shared is not None:
                return self.pool.alloc.can_reserve(
                    self.pool.pages_needed(req) - len(shared))
        if self.prefill_chunk and req.prompt_len > self.prefill_chunk \
                and self._chunk_job is not None:
            return False
        return self.pool.can_admit(req)

    # -------------------------------------------------------------- preemption

    def _preempt_for_head(self) -> bool:
        """The head of the admission heap is blocked on slots or pages:
        evict ONE active stream of strictly lower priority (greatest
        priority value; ties broken toward the most recent admission —
        least work lost) and report whether anything was evicted. The
        admission loop retries after each eviction, so exactly as many
        victims fall as the head needs. An ExpertAwareScheduler remembers
        WHICH candidate its cost model chose before the page gate blocked it
        (`last_blocked`) — pages are freed for that request, not for the
        arrival-order head it may have skipped; within a priority class the
        victim with the most experts UNIQUE to it falls first (evicting it
        shrinks the tick's expert set the most)."""
        if not (self.pool.paged and self.scheduler.queue):
            return False
        head = getattr(self.scheduler, "last_blocked", None) or \
            self.scheduler.queue[0][2]
        if head.request_id not in self._preempted and self.prefill_chunk \
                and head.prompt_len > self.prefill_chunk \
                and self._full_hit(head) is None \
                and self._ext_hit(head) is None \
                and self._chunk_job is not None:
            return False     # blocked on the chunk LANE — eviction can't help
        victims = [(owner.priority, self._victim_rank(slot),
                    owner.admit_step, slot)
                   for slot, owner in enumerate(self.pool.owner)
                   if owner is not None and owner.priority > head.priority]
        if not victims:
            return False
        self._preempt(max(victims)[3])
        return True

    def _victim_rank(self, slot: int) -> int:
        """Preemption cost model (expert-aware engines): victims touching
        more experts nobody else needs rank higher. 0 under plain FIFO, so
        the historical (priority, admit_step) order is unchanged."""
        if not self.expert_aware:
            return 0
        others = [o.expert_sig for s, o in enumerate(self.pool.owner)
                  if o is not None and s != slot]
        return self.scheduler.victim_bonus(
            self.pool.owner[slot].expert_sig, others)

    def _preempt(self, slot: int) -> None:
        """Evict the stream in `slot`: host-snapshot its live pages + GO
        rows + cursor, free its pages, park it PREEMPTED, and put it back
        in the admission heap under its original submit order."""
        req = self.pool.owner[slot]
        snap = self.pool.snapshot(slot)
        self.pool.retire(slot)
        req.slot = -1
        req.status = RequestStatus.PREEMPTED
        req.preemptions += 1
        self._preempted[req.request_id] = snap
        self.scheduler.requeue(req)
        self.preempted_total += 1

    def _resume(self, slot: int, req: Request) -> None:
        """Un-park a preempted stream into a free slot via block-table
        surgery (SlotPool.restore) — no re-prefill, bit-identical to an
        uninterrupted run."""
        snap = self._preempted.pop(req.request_id)
        self.pool.restore(slot, req, snap)
        req.status = RequestStatus.ACTIVE
        self.resumed_total += 1
        self._note_occupancy()

    # ------------------------------------------------------ faults & deadlines

    def _expire(self, now: float, done: list[Request]) -> None:
        """Retire every request whose wall budget ran out, wherever it is:
        queued/pending/preempted (scheduler heaps), mid-chunk-prefill, or
        actively decoding."""
        for req in self.scheduler.expire(now):
            self._preempted.pop(req.request_id, None)
            self._mark_finished(req, RequestStatus.TIMEOUT, done,
                                reason="deadline exceeded before admission"
                                if req.admit_time == 0 else
                                "deadline exceeded while preempted")
        job = self._chunk_job
        if job is not None and job.req.expired(now):
            self.pool.release_pages(job.req.request_id)
            self._chunk_job = None
            self._mark_finished(job.req, RequestStatus.TIMEOUT, done,
                                reason="deadline exceeded during prefill")
        for slot, req in enumerate(self.pool.owner):
            if req is not None and req.expired(now):
                self._retire_slot(slot, RequestStatus.TIMEOUT, done,
                                  reason="deadline exceeded")

    def _inject_state_faults(self) -> None:
        """Chaos state-level injections for this tick: a forced eviction
        (exercises the snapshot/restore path — semantics-preserving) and/or
        a poisoned slot (NaN KV -> the quarantine path, off by default in
        the env lane)."""
        active = [s for s, o in enumerate(self.pool.owner) if o is not None]
        if self.preemption and self.pool.paged:
            victim = self.chaos.preempt_victim(active)
            if victim is not None:
                self._preempt(victim)
                active.remove(victim)
        victim = self.chaos.nan_victim(active)
        if victim is not None:
            self.pool.poison_slot(victim)

    def _supervised_decode(self):
        """Run the jitted decode tick under the StepSupervisor: injected or
        real transient errors are retried with IDENTICAL inputs (the tick is
        functional — pool state and sampling keys are only committed after
        success), hard failures raise RestartRequired."""
        def tick():
            if self.chaos is not None:
                self.chaos.maybe_tick_fault(self.step_count)
            out = self._run_decode_step()
            with span("engine.decode.wait"):
                return jax.block_until_ready(out)
        return self.supervisor.run(tick, step=self.step_count)

    def _mesh_scope(self):
        """The engine's mesh as the ambient mesh (`jax.set_mesh`) while a
        step over its sharded parameters traces and runs — a Mosaic kernel
        lowers only under it (`kernels/moe_gmm.py::mosaic_call`); a no-op
        when unsharded."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    def _run_decode_step(self):
        """One jitted decode tick, inside the mesh context when sharded (the
        jit cache keys on the ambient mesh, so the sharded and unsharded
        variants coexist in one process). Pure-greedy pools run the lean
        greedy step; a pool with any sampling request runs the sampling
        variant (greedy rows inside it stay bit-identical). Returns
        (tokens, state, ok, new_keys-or-None) WITHOUT committing anything
        to the pool — the caller commits, so a supervisor retry is pure."""
        sampling = bool((self.pool.temps > 0).any())
        args = (self.params, self.pool.state, jnp.asarray(self.pool.pending),
                jnp.asarray(self.pool.active_mask()))
        if sampling:
            args += (jnp.asarray(self.pool.temps),
                     jnp.asarray(self.pool.top_ps),
                     jnp.asarray(self.pool.keys))
        fn = _decode_step_sampled if sampling else _decode_step
        with self._mesh_scope():
            out = fn(*args, self.cfg)
        if sampling:
            return out                       # (toks, state, ok, new_keys)
        toks, state, ok = out
        return toks, state, ok, None

    def _bucketed(self, prompt: np.ndarray):
        """Pad the prompt up to its power-of-two bucket (capped at the
        pool's max_tokens); returns (padded [S_b], valid_len or None)."""
        n = int(prompt.shape[0])
        b = 8
        while b < n:
            b *= 2
        b = min(b, self.pool.max_tokens)
        if b <= n:
            return prompt, None
        return np.pad(prompt, (0, b - n)), n

    def _first_token(self, req: Request, logits):
        """The request's first output token from its prefill logits — argmax,
        or sampled when the request asks for temperature > 0. Returns
        (token, advanced PRNG key or None)."""
        if req.temperature > 0:
            seed = req.seed if req.seed is not None else req.request_id
            k_use, key_next = jax.random.split(jax.random.PRNGKey(seed))
            first = int(_sample_tokens(
                logits, k_use[None],
                jnp.full((1,), req.temperature, jnp.float32),
                jnp.full((1,), req.top_p, jnp.float32))[0])
            return first, key_next
        return int(jnp.argmax(logits, axis=-1)[0]), None

    def _admit(self, slot: int, req: Request, done: list[Request]) -> None:
        """Prefill a request into `slot` mid-flight: fills that row's KV and
        GO cache entries and emits the request's first token (from the
        prefill logits — exactly what static generate() emits first; sampled
        from them when the request asks for temperature > 0)."""
        prompt, valid_len = (self._bucketed(req.prompt) if self.prompt_buckets
                             else (req.prompt, None))
        self.prefill_lengths.add(int(prompt.shape[0]))
        self._tick.oneshot.append(req.prompt_len)
        with self._mesh_scope():
            slot_state, logits = _jit_prefill(
                self.params, jnp.asarray(prompt, jnp.int32)[None, :],
                self.cfg, req.extras or {}, self.pool.max_tokens,
                None if valid_len is None
                else jnp.asarray(valid_len, jnp.int32))
        self._install(slot, req, slot_state, logits, done)

    def _install(self, slot: int, req: Request, slot_state, logits,
                 done: list[Request], page_row=None, *,
                 deposit: bool = True) -> None:
        """Shared tail of one-shot, prefix-extension and chunked admission:
        emit the first token, splat the prefilled state into the pool row,
        handle an immediate EOS/length finish. `page_row` marks a paged run
        whose pages are already claimed and filled. Non-finite prefill
        logits quarantine the request to FAILED before it ever occupies the
        slot. With prefix sharing on, the freshly-admitted prompt deposits
        its prefill artifacts into the prefix index (`deposit=False` for
        chunk runs — a chunked expert-choice prefill routes at per-chunk
        capacities, so its GO rows and logits are not the one-shot
        artifacts the cache promises)."""
        with span("engine.prefill.wait"):
            finite = bool(np.isfinite(np.asarray(logits)).all())
        if not finite:
            if page_row is not None and self.pool.paged:
                self.pool.release_pages(req.request_id)  # claimed run pages
            self._mark_finished(req, RequestStatus.FAILED, done,
                                reason="non-finite prefill logits")
            return
        first, key_next = self._first_token(req, logits)
        req.admit_step = self.step_count
        req.admit_time = time.monotonic()
        req.status = RequestStatus.ACTIVE
        req.tokens.append(first)
        self._journal_token(req, first, install=True)
        self.pool.admit(slot, req, slot_state, first, key=key_next,
                        page_row=page_row)
        if self.expert_aware:
            self._refine_sig(slot, req)
            self.scheduler.observe(req.expert_sig)
        if deposit:
            self._deposit(slot, req, logits)
        self._note_occupancy()       # before a possible instant retirement
        if self.pool.remaining[slot] <= 0 or \
                (req.eos_id is not None and first == req.eos_id):
            self._finish(slot, done)

    def _refine_sig(self, slot: int, req: Request) -> None:
        """Replace the submit-time gate-probe prediction with the routing
        the prefill actually OBSERVED, where observable: an expert-choice
        arch's GO cache records exactly which (layer, expert, token) pairs
        were kept — union over layers/capacity beats any probe. Unless the
        union SATURATES: expert-choice hands every expert its capacity of
        tokens whenever the prompt is long enough, and an all-experts
        signature carries no grouping signal — keep the sparse layer-0
        probe instead (the scheduler only needs a consistent fingerprint,
        not ground truth)."""
        if "go" not in self.pool.state:
            return
        tid = np.asarray(self.pool.state["go"].token_ids[:, slot])  # [L,E,k]
        sig = (tid >= 0).any(axis=(0, 2))
        if not sig.all():
            req.expert_sig = sig

    # --------------------------------------------------------- prefix sharing

    def _full_hit(self, req: Request):
        """Exact full-prompt prefix-index entry for `req`, or None. Requests
        with per-request extras (cross-attn memory) never hit: their prefill
        state depends on more than the prompt tokens."""
        if self.prefix_index is None or req.extras is not None:
            return None
        return self.prefix_index.lookup_full(req.prompt)

    def _ext_hit(self, req: Request):
        """Shared page chain for a page-aligned PREFIX of `req`'s prompt, or
        None. DENSE archs only: an MoE prefill routes with whole-sequence
        competition (expert-choice capacity, batch-level token ranks), so a
        prefix's KV under a longer prompt is not the KV this prompt's
        prefill would produce — only the full-prompt exact match (where the
        donor ran the identical prefill) is reusable for MoE. For dense
        attention the prefix KV is position-local and exact, and the repo
        pins chunked==one-shot prefill, so resuming prefill past the prefix
        stays bit-identical."""
        if self.prefix_index is None or req.extras is not None \
                or self.cfg.moe is not None:
            return None
        shared = self.prefix_index.lookup_prefix(req.prompt)
        ps = self.pool.page_size
        while shared and len(shared) * ps >= req.prompt_len:
            # the whole prompt is covered but no full entry exists (evicted,
            # or the match is a prefix of a LONGER cached prompt): re-prefill
            # the last page so the admission has prefill logits to emit from
            shared.pop()
        if not shared:
            return None
        if self.prefill_chunk and \
                req.prompt_len - len(shared) * ps > self.prefill_chunk:
            return None    # remainder is still a long prompt: chunk lane
        return shared

    def _admit_any(self, slot: int, req: Request,
                   done: list[Request]) -> None:
        """Admission dispatch: full-prompt cache hit (zero prefill), dense
        prefix-extension hit (prefill only the remainder), or cold one-shot
        prefill."""
        entry = self._full_hit(req)
        if entry is not None:
            self._admit_from_cache(slot, req, entry, done)
            return
        shared = self._ext_hit(req)
        if shared is not None:
            self._admit_prefix_ext(slot, req, shared, done)
            return
        self._admit(slot, req, done)

    def _admit_from_cache(self, slot: int, req: Request, entry: dict,
                          done: list[Request]) -> None:
        """Zero-compute admission from a full-prompt prefix-index entry:
        O(1) block-table surgery instead of O(prompt) prefill. The first
        token comes from the entry's cached prefill logits — the SAME
        logits the donor's prefill emitted, so greedy streams are
        bit-identical to a cold admission (and sampling requests draw from
        the exact distribution under their own temperature/seed). The
        donor's finite-logits check already vetted the entry."""
        shared = self.prefix_index.entry_pages(entry)
        first, key_next = self._first_token(req, jnp.asarray(entry["logits"]))
        req.admit_step = self.step_count
        req.admit_time = time.monotonic()
        req.status = RequestStatus.ACTIVE
        req.tokens.append(first)
        self._journal_token(req, first, install=True)
        self.pool.admit_from_prefix(slot, req, shared, entry, first,
                                    key=key_next)
        if req.expert_sig is None and entry["sig"] is not None:
            req.expert_sig = entry["sig"]
        if self.expert_aware:
            self.scheduler.observe(req.expert_sig)
        self.prefix_hits += 1
        self.pages_shared += len(shared)
        self.prefill_tokens_skipped += req.prompt_len
        self._note_occupancy()
        if self.pool.remaining[slot] <= 0 or \
                (req.eos_id is not None and first == req.eos_id):
            self._finish(slot, done)

    def _admit_prefix_ext(self, slot: int, req: Request, shared,
                          done: list[Request]) -> None:
        """Dense prefix-extension admission: map the cached prefix's pages
        copy-on-write and prefill ONLY the remainder of the prompt in one
        paged chunk run (prefill_chunk starting past the prefix, attending
        over the shared pages — the same machinery chunked prefill uses,
        minus the chunks the cache already holds)."""
        ps = self.pool.page_size
        start = len(shared) * ps
        row = self.pool.claim_prefix_ext_pages(req, shared)
        rem = req.prompt_len - start
        padded = -(-rem // ps) * ps
        chunk = np.pad(req.prompt[start:], (0, padded - rem))
        # quantized pools: the batch-1 skeleton stays UNQUANTIZED (chunk-run
        # GO rows are f32 by the chunk-lane contract — write_decode_slot
        # quantizes them once at the final splat); only the pool's int8 page
        # store + its scales thread through the run
        quant = self.pool.quant
        skel_cfg = (self.cfg.with_overrides(kv_quant="none")
                    if quant else self.cfg)
        state = init_decode_state(skel_cfg, 1, self.pool.max_tokens,
                                  req.extras or {},
                                  paged=(1, ps))
        del state["k_pages"], state["v_pages"]
        state["block_table"] = jnp.asarray(row, jnp.int32)[None, :]
        state["k_pages"] = self.pool.state["k_pages"]
        state["v_pages"] = self.pool.state["v_pages"]
        if quant:
            state["k_scales"] = self.pool.state["k_scales"]
            state["v_scales"] = self.pool.state["v_scales"]
        args = (self.params, state, jnp.asarray(chunk, jnp.int32)[None, :],
                self.cfg, jnp.asarray(start, jnp.int32),
                jnp.asarray(rem, jnp.int32))
        self._tick.chunks.append((start, rem))
        with self._mesh_scope():
            state, logits = _jit_prefill_chunk(*args)
        self.pool.state["k_pages"] = state.pop("k_pages")
        self.pool.state["v_pages"] = state.pop("v_pages")
        if quant:
            self.pool.state["k_scales"] = state.pop("k_scales")
            self.pool.state["v_scales"] = state.pop("v_scales")
        self.pool.state = self.pool._pin(self.pool.state)
        self.prefix_hits += 1
        self.pages_shared += len(shared)
        self.prefill_tokens_skipped += start
        self._install(slot, req, state, logits, done, page_row=row)

    def _deposit(self, slot: int, req: Request, logits) -> None:
        """Record a freshly-admitted prompt in the prefix index: pin its
        full pages as radix nodes (refcount bump — nothing moves) and cache
        the artifacts pages alone can't give a future consumer — the tail
        KV past the last full page (it sits in this request's PRIVATE page,
        which its decode will overwrite), the GO rows (TopKUpdate history —
        not recomputable), and the prefill logits (the consumer's first
        token without a forward pass). Deposited at ADMISSION, so the entry
        serves consumers while the donor is still live AND after it retires
        (the node refcounts keep the pages alive — "recently-retired"
        donors cost nothing extra)."""
        idx = self.prefix_index
        if idx is None or req.extras is not None:
            return
        ps = self.pool.page_size
        row = self.pool.block_table[slot]
        n_full = req.prompt_len // ps
        tail = req.prompt_len - n_full * ps
        tail_k = tail_v = tail_ks = tail_vs = None
        if tail:
            pid = int(row[n_full])
            tail_k = np.asarray(self.pool.state["k_pages"][:, pid, :tail])
            tail_v = np.asarray(self.pool.state["v_pages"][:, pid, :tail])
            if self.pool.quant:
                # int8 tail bytes are meaningless without their page scales
                tail_ks = np.asarray(self.pool.state["k_scales"][:, pid])
                tail_vs = np.asarray(self.pool.state["v_scales"][:, pid])
        go = None
        if "go" in self.pool.state:
            go = jax.tree.map(lambda a: np.asarray(a[:, slot]),
                              self.pool.state["go"])
        go_scales = None
        if "go_scales" in self.pool.state:
            go_scales = np.asarray(self.pool.state["go_scales"][:, slot])
        released = idx.deposit(
            req.prompt, row[:n_full], tail_k=tail_k, tail_v=tail_v, go=go,
            logits=np.asarray(logits, np.float32).reshape(1, -1),
            sig=req.expert_sig, tail_ks=tail_ks, tail_vs=tail_vs,
            go_scales=go_scales)
        self.pool.scrub_released(released)

    # ---------------------------------------------------------- chunk prefill

    def _start_chunk_job(self, slot: int, req: Request) -> None:
        """Claim `slot` and the request's worst-case pages, then begin
        filling one chunk per tick. Dense pools fill a private batch-1
        state; paged pools claim the request's pages up front
        (claim_chunk_pages) and prefill straight into the pool's page store
        — no dense [1, max_tokens] KV copy ever exists."""
        Cs = self.prefill_chunk
        padded = -(-req.prompt_len // Cs) * Cs
        prompt = np.pad(req.prompt, (0, padded - req.prompt_len))
        page_row = None
        if self.pool.paged:
            page_row = self.pool.claim_chunk_pages(req)
            # batch-1 paged view: position/GO/block-table only — the page
            # store itself is threaded in from the pool at each chunk tick.
            # Quantized pools keep the skeleton UNQUANTIZED: its GO rows
            # accumulate in f32 across chunks (go_cache_merge reads float
            # outputs) and quantize once at the final write_decode_slot
            # splat; the pool's int8 pages + scales thread through per tick.
            skel_cfg = (self.cfg.with_overrides(kv_quant="none")
                        if self.pool.quant else self.cfg)
            state = init_decode_state(skel_cfg, 1, self.pool.max_tokens,
                                      req.extras or {},
                                      paged=(1, self.pool.page_size))
            for key in page_stores(state):
                del state[key]
            state["block_table"] = jnp.asarray(page_row, jnp.int32)[None, :]
        else:
            state = init_decode_state(self.cfg, 1, self.pool.max_tokens,
                                      req.extras or {})
            self.pool.reserve_pages(req)
        self._chunk_job = _ChunkJob(req=req, slot=slot, state=state,
                                    prompt=prompt, page_row=page_row)
        self._advance_chunk_job_once()

    def _advance_chunk_job(self, done: list[Request]) -> None:
        self._advance_chunk_job_once()
        job = self._chunk_job
        if job is not None and job.pos >= len(job.prompt):
            self._chunk_job = None
            self._install(job.slot, job.req, job.state, job.logits, done,
                          page_row=job.page_row, deposit=False)

    def _advance_chunk_job_once(self) -> None:
        job = self._chunk_job
        Cs = self.prefill_chunk
        chunk = job.prompt[job.pos:job.pos + Cs]
        valid = min(Cs, job.req.prompt_len - job.pos)
        paged = job.page_row is not None
        if paged:
            # thread the pool's page store through the chunk run: the chunk
            # scatters its KV into the job's claimed pages (disjoint from
            # every active slot's), interleaved decode ticks touch only
            # other pages, so ownership transfers cleanly back each tick
            for key in page_stores(self.pool.state):
                job.state[key] = self.pool.state[key]
            if self.pool.quant:
                job.state["k_scales"] = self.pool.state["k_scales"]
                job.state["v_scales"] = self.pool.state["v_scales"]
        args = (self.params, job.state,
                jnp.asarray(chunk, jnp.int32)[None, :], self.cfg,
                jnp.asarray(job.pos, jnp.int32), jnp.asarray(valid, jnp.int32))
        self._tick.chunks.append((job.pos, valid))
        with self._mesh_scope():
            job.state, job.logits = _jit_prefill_chunk(*args)
        if paged:
            for key in page_stores(self.pool.state):
                self.pool.state[key] = job.state.pop(key)
            if self.pool.quant:
                self.pool.state["k_scales"] = job.state.pop("k_scales")
                self.pool.state["v_scales"] = job.state.pop("v_scales")
            self.pool.state = self.pool._pin(self.pool.state)
        job.pos += Cs
        self.chunk_ticks += 1

    def _finish(self, slot: int, done: list[Request]) -> None:
        self._retire_slot(slot, RequestStatus.DONE, done)

    def _retire_slot(self, slot: int, status: RequestStatus,
                     done: list[Request], reason: str | None = None) -> None:
        """Retire an ACTIVE slot into terminal `status`: frees the slot
        (pages back to the allocator, GO rows to -inf) and records the
        outcome. A FAILED retirement is a quarantine — its decode state is
        non-finite, so its pages are scrubbed before the allocator can hand
        them to another stream (NaN survives 0-weight masking)."""
        req = self.pool.retire(slot, scrub=status is RequestStatus.FAILED)
        self._mark_finished(req, status, done, reason=reason)

    def _mark_finished(self, req: Request, status: RequestStatus,
                       done: list[Request], reason: str | None = None) -> None:
        req.status = status
        req.fail_reason = reason
        req.finish_step = self.step_count
        req.finish_time = time.monotonic()
        self.finished[req.request_id] = req
        done.append(req)
        if self.journal is not None:
            self.journal.append("terminal", rid=req.request_id,
                                status=status.value, reason=reason)

    # -------------------------------------------------------------- durability

    def _attach_journal(self, directory: str, snapshot_every: int = 0) -> None:
        """Open the engine's write-ahead journal and commit the initial
        snapshot. Journaling rides on the paged pool's host-side snapshot
        contract (SlotPool.snapshot) — dense pools and engines with extras
        (cross-attn memory is not snapshotted) refuse it."""
        if not self.pool.paged:
            raise ValueError("journaling needs a paged pool (engine "
                             "snapshots are SlotPool.snapshot block-table "
                             "surgery)")
        if self._engine_extras is not None:
            raise ValueError("journaling rejects engine extras: cross-attn "
                             "memory is not part of the snapshot payload")
        if self.cfg.mla:
            raise ValueError("journaling does not handle the latent page "
                             "pool of a latent-attention model yet")
        self.journal = EngineJournal(
            directory, snapshot_every=snapshot_every or 32)
        self.journal.commit_snapshot(self._snapshot_payload(),
                                     self.step_count)

    def _journal_token(self, req: Request, tok: int, *,
                       install: bool = False) -> None:
        """Journal one emitted token and check it against the recovery
        oracle: tokens the CRASHED process journaled are a prefix-assertion
        on the recovered streams — re-decoded output must reproduce every
        watermarked token bit-for-bit before producing anything new."""
        exp = self._replay_expect.get(req.request_id)
        if exp:
            want = exp.pop(0)
            if not exp:
                del self._replay_expect[req.request_id]
            assert tok == want, (
                f"recovery divergence: request {req.request_id} emitted "
                f"token {tok} where the journal watermark says {want}")
        if self.journal is None:
            return
        if install:
            self.journal.append("install", rid=req.request_id,
                                step=self.step_count, token=tok)
        else:
            self._tick_toks[req.request_id] = tok

    def _maybe_crash(self) -> None:
        """Chaos crash-class injection: die by SIGKILL at this tick —
        straight away ("kill"), after tearing the journal's last record
        mid-write ("torn"), or after materializing the next snapshot
        WITHOUT its COMMITTED marker ("snap"). Journaled engines only: the
        whole point is proving recover() undoes the damage."""
        if self.journal is None or self.chaos is None:
            return
        crash = self.chaos.crash_event(self.step_count)
        if crash is None:
            return
        if crash == "torn":
            self.journal.tear_tail(
                self.chaos.torn_cut(self.journal._last_record_bytes))
        elif crash == "snap":
            self.journal.write_uncommitted_snapshot(self._snapshot_payload())
        os.kill(os.getpid(), signal.SIGKILL)

    def _snapshot_payload(self) -> dict:
        """Whole-engine state at this tick, host-side and picklable: every
        live slot's SlotPool.snapshot (pages + GO rows + cursor + PRNG key),
        the scheduler heaps, parked preemption snapshots, the prefix index
        (structure + pinned page contents), scheduler EWMAs, and counters.
        The chunk job is recorded as its REQUEST only — recovery re-queues
        it and re-runs the chunked prefill from scratch, which is
        deterministic per chunking. The PageAllocator is not serialized:
        restore() re-reserves and re-allocates, which reproduces its
        semantics under fresh physical ids (ids are invisible to streams)."""
        slots = []
        for slot, req in enumerate(self.pool.owner):
            if req is not None:
                slots.append((slot, request_record(req, runtime=True),
                              self.pool.snapshot(slot)))
        job = self._chunk_job
        reqs = ([r for _, _, r in self.scheduler.queue] +
                [r for _, _, r in self.scheduler._pending] +
                [o for o in self.pool.owner if o is not None] +
                list(self.finished.values()) +
                ([job.req] if job is not None else []))
        prefix = None
        if self.prefix_index is not None:
            prefix = self.prefix_index.snapshot_state()
            ids = sorted({p for _, p, _ in prefix["nodes"]})
            if ids:
                jids = jnp.asarray(ids, jnp.int32)
                prefix["page_contents"] = {
                    "ids": ids,
                    "k": np.asarray(self.pool.state["k_pages"][:, jids]),
                    "v": np.asarray(self.pool.state["v_pages"][:, jids]),
                }
                if self.pool.quant:
                    prefix["page_contents"]["ks"] = np.asarray(
                        self.pool.state["k_scales"][:, jids])
                    prefix["page_contents"]["vs"] = np.asarray(
                        self.pool.state["v_scales"][:, jids])
        return {
            "meta": {
                "step": self.step_count,
                "recoveries": self.recoveries,
                "next_id": self._next_id,
                "seq_next": max((r.seq for r in reqs), default=-1) + 1,
                "snapshot_every": (self.journal.snapshot_every
                                   if self.journal is not None else 32),
            },
            "engine_kw": dict(self._engine_kw),
            "slots": slots,
            "queued": [request_record(r, runtime=True)
                       for _, _, r in self.scheduler.queue],
            "pending": [request_record(r, runtime=True)
                        for _, _, r in self.scheduler._pending],
            "chunk_req": (request_record(job.req, runtime=True)
                          if job is not None else None),
            "preempted": dict(self._preempted),
            "finished": [request_record(r, runtime=True)
                         for r in self.finished.values()],
            "prefix": prefix,
            "sched_load": (self.scheduler.load.copy()
                           if self.expert_aware else None),
            "counters": {
                "admitted_total": self.pool.admitted_total,
                "preempted_total": self.preempted_total,
                "resumed_total": self.resumed_total,
                "rejected_full": self.rejected_full,
                "rejected_oversized": self.rejected_oversized,
                "peak_active": self.peak_active,
                "chunk_ticks": self.chunk_ticks,
                "prefix_hits": self.prefix_hits,
                "pages_shared": self.pages_shared,
                "prefill_tokens_skipped": self.prefill_tokens_skipped,
            },
        }

    def _restore_prefix_index(self, pstate: dict) -> None:
        """Rebuild the prefix index from a snapshot: allocate fresh physical
        pages under a temporary owner, scatter the saved page contents back,
        hand the pins over to the radix nodes, release the temporary owner.
        The cache is performance state — if the pool can't cover it at
        recovery (it always can when geometry is unchanged, but overrides
        may shrink it), recovery proceeds cold instead of failing."""
        contents = pstate.get("page_contents")
        if contents is None:
            return
        ids = [int(p) for p in contents["ids"]]
        tmp = -(10 ** 9)        # disjoint from request ids and node rids
        try:
            self.pool.alloc.reserve(tmp, len(ids))
        except RuntimeError:
            return
        fresh = self.pool.alloc.alloc(tmp, len(ids))
        jids = jnp.asarray(fresh, jnp.int32)
        self.pool.state["k_pages"] = self.pool.state["k_pages"].at[
            :, jids].set(jnp.asarray(contents["k"]).astype(
                self.pool.state["k_pages"].dtype))
        self.pool.state["v_pages"] = self.pool.state["v_pages"].at[
            :, jids].set(jnp.asarray(contents["v"]).astype(
                self.pool.state["v_pages"].dtype))
        if self.pool.quant:
            self.pool.state["k_scales"] = self.pool.state["k_scales"].at[
                :, jids].set(jnp.asarray(contents["ks"]))
            self.pool.state["v_scales"] = self.pool.state["v_scales"].at[
                :, jids].set(jnp.asarray(contents["vs"]))
        self.pool.state = self.pool._pin(self.pool.state)
        self.prefix_index.restore_state(pstate, dict(zip(ids, fresh)))
        self.pool.alloc.free(tmp)   # node pins keep every page alive

    @classmethod
    def recover(cls, journal_dir: str, params, cfg, *, mesh=None,
                chaos: Chaos | None = None, snapshot_every: int = 0,
                **overrides) -> "ServingEngine":
        """Rebuild a crashed engine from its journal directory: restore the
        latest COMMITTED snapshot (uncommitted crash artifacts are skipped),
        replay the journal tail, and commit a fresh post-recovery snapshot.

        Live-at-snapshot streams resume via SlotPool.restore — decode is
        deterministic given the restored state (pages + GO rows + cursor +
        per-slot PRNG key), so greedy AND sampled streams continue
        bit-identically to the uninterrupted run. Requests admitted after
        the snapshot are re-queued and re-prefilled (deterministic again).
        Tokens the dead process journaled past the snapshot become a
        prefix-assertion oracle: the recovered streams must re-emit exactly
        them before producing anything new. Terminal events replay only
        CANCELLED (an external decision the engine can't recompute); DONE /
        TIMEOUT / FAILED outcomes are recomputed by simply running — wall
        budgets re-anchor at recovery time."""
        t0 = time.monotonic()
        latest = EngineJournal.latest_committed(journal_dir)
        if latest is None:
            raise JournalError(
                f"no committed snapshot under {journal_dir!r} — nothing to "
                "recover from")
        seq, payload = latest
        kw = dict(payload["engine_kw"])
        kw.update(overrides)
        eng = cls(params, cfg, mesh=mesh, chaos=chaos, journal_dir=False,
                  **kw)
        meta = payload["meta"]
        eng.step_count = meta["step"]
        eng.recoveries = meta["recoveries"] + 1
        eng._next_id = meta["next_id"]
        eng.scheduler._seq = itertools.count(meta["seq_next"])
        for rec in payload["finished"]:
            req = request_from_record(rec)
            eng.finished[req.request_id] = req
        for rec in payload["queued"]:
            req = request_from_record(rec)
            heapq.heappush(eng.scheduler.queue,
                           (req.priority, req.seq, req))
        for rec in payload["pending"]:
            req = request_from_record(rec)
            heapq.heappush(eng.scheduler._pending,
                           (req.arrival_step, req.seq, req))
        if payload["chunk_req"] is not None:
            # the interrupted chunk run re-prefills from scratch — its heap
            # position (original seq) keeps the admission order
            req = request_from_record(payload["chunk_req"])
            req.status = RequestStatus.QUEUED
            heapq.heappush(eng.scheduler.queue,
                           (req.priority, req.seq, req))
        eng._preempted = dict(payload["preempted"])
        for slot, rec, snap in payload["slots"]:
            req = request_from_record(rec)
            eng.pool.restore(slot, req, snap)
        if eng.prefix_index is not None and payload["prefix"] is not None:
            eng._restore_prefix_index(payload["prefix"])
        if eng.expert_aware and payload["sched_load"] is not None \
                and len(payload["sched_load"]) == len(eng.scheduler.load):
            eng.scheduler.load[:] = payload["sched_load"]
        for name, val in payload["counters"].items():
            if name == "admitted_total":
                eng.pool.admitted_total = val   # pool.restore bumped it
            else:
                setattr(eng, name, val)
        # --- replay the journal tail (torn tail already dropped) ---
        events = EngineJournal.read_tail(journal_dir, seq)
        cancelled: list[int] = []
        for kind, p in events:
            if kind == "submit":
                req = request_from_record(p["req"])
                if req.arrival_step > eng.step_count:
                    heapq.heappush(eng.scheduler._pending,
                                   (req.arrival_step, req.seq, req))
                else:
                    heapq.heappush(eng.scheduler.queue,
                                   (req.priority, req.seq, req))
            elif kind == "install":
                eng._replay_expect.setdefault(p["rid"], []).append(p["token"])
            elif kind == "tick":
                for rid, tok in p["toks"].items():
                    eng._replay_expect.setdefault(rid, []).append(tok)
            elif kind == "terminal" and \
                    p["status"] == RequestStatus.CANCELLED.value:
                cancelled.append(p["rid"])
        eng.replayed_events = len(events)
        # committing a fresh snapshot collapses the replayed tail: a second
        # crash during recovery re-runs from HERE, never from the torn log
        eng._attach_journal(journal_dir,
                            snapshot_every or meta["snapshot_every"])
        for rid in cancelled:
            eng.cancel(rid)
        eng.recovered_info = {
            "snapshot_seq": seq,
            "events": len(events),
            "wall_ms": (time.monotonic() - t0) * 1000.0,
        }
        return eng

    def _audit(self) -> None:
        """REPRO_AUDIT=1 invariant sweep, every tick: pool/allocator
        consistency (SlotPool.audit) plus the engine-level cross-checks —
        the chunk lane's claimed slot stays unoccupied and parked preempted
        requests are neither active nor finished."""
        self.pool.audit()
        if self.pool.paged:
            # refcount invariant: the allocator's page refcounts must equal
            # the LIVE references — slot block-table entries, the chunk
            # run's claimed row, and the prefix index's node pins. A page
            # freed while referenced (or referenced while free) shows up
            # here as a count mismatch.
            refs: Counter[int] = Counter()
            for slot, owner in enumerate(self.pool.owner):
                if owner is not None:
                    r = self.pool.block_table[slot]
                    refs.update(int(p) for p in r[r != 0])
            job_row = (self._chunk_job.page_row
                       if self._chunk_job is not None else None)
            if job_row is not None:
                refs.update(int(p) for p in job_row[job_row != 0])
            if self.prefix_index is not None:
                refs.update(self.prefix_index.node_pages())
            rc = Counter(self.pool.alloc.refcounts())
            assert refs == rc, \
                f"page refcounts != live references: {rc - refs} over, " \
                f"{refs - rc} under"
        job = self._chunk_job
        if job is not None:
            assert self.pool.owner[job.slot] is None, \
                "chunk job's claimed slot was given away"
        for rid in self._preempted:
            assert all(o is None or o.request_id != rid
                       for o in self.pool.owner), \
                f"preempted request {rid} also occupies a slot"
            assert rid not in self.finished, \
                f"preempted request {rid} already finished"

    # ------------------------------------------------------------------ stats

    def stats(self) -> dict:
        from repro.core.moe import resolve_backend
        reqs = self.finished.values()
        return {
            "steps": self.step_count,
            "admitted": self.pool.admitted_total,
            "finished": len(self.finished),
            "queued": len(self.scheduler.queue),
            "active": self.pool.num_active(),
            "tokens_out": sum(len(r.tokens) for r in reqs),
            "moe_backend": (resolve_backend(self.cfg.moe)
                            if self.cfg.moe is not None else None),
            "mesh": dict(self.mesh.shape) if self.mesh is not None else None,
            "prefill_lengths": sorted(self.prefill_lengths),
            "peak_active": self.peak_active,
            "paged": self.pool.paged,
            "page_size": self.pool.page_size if self.pool.paged else None,
            "num_pages": self.pool.num_pages,
            "pages_in_use": (self.pool.alloc.pages_in_use
                             if self.pool.paged else None),
            "chunk_ticks": self.chunk_ticks,
            # --- quantized decode state ---
            "kv_quant_dtype": (self.cfg.kv_quant
                               if self.cfg.kv_quant != "none" else None),
            "kv_bytes_per_token": (
                Q.kv_bytes_per_token(self.cfg, self.pool.page_size)
                if self.pool.paged else None),
            "dequant_max_abs_err": (self.pool.dequant_max_abs_err
                                    if self.pool.quant else None),
            # --- prefix sharing / expert-aware admission ---
            "prefix_share": self.prefix_share,
            "expert_aware": self.expert_aware,
            "prefix_hits": self.prefix_hits,
            "pages_shared": self.pages_shared,
            "prefill_tokens_skipped": self.prefill_tokens_skipped,
            # --- fault domain ---
            "statuses": dict(Counter(r.status.value for r in reqs)),
            "preemptions": self.preempted_total,
            "resumes": self.resumed_total,
            "preempted_waiting": len(self._preempted),
            "rejected": {"queue_full": self.rejected_full,
                         "oversized": self.rejected_oversized},
            "tick_retries": self.supervisor.stats.retries,
            "tick_stragglers": [
                {"step": s, "wall_ms": round(dt * 1e3, 3),
                 "median_ms": round(med * 1e3, 3)}
                for s, dt, med in self.supervisor.stats.stragglers],
            "chaos": (dict(self.chaos.injected)
                      if self.chaos is not None else None),
            # --- durability ---
            "recoveries": self.recoveries,
            "restart_count": self.restart_count,
            "replayed_events": self.replayed_events,
            "journal_bytes": (self.journal.bytes_written
                              if self.journal is not None else 0),
            "snapshots": (self.journal.snapshots_committed
                          if self.journal is not None else 0),
            "snapshot_age_ticks": (
                self.step_count - self.journal.last_snapshot_step
                if self.journal is not None else None),
        }
