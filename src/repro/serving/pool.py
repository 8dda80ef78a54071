"""Slot pool: owns the pooled per-request KV + GO decode state.

One decode state of `num_slots` batch rows lives on device for the whole
engine lifetime; requests are admitted into free rows and retired out of
them without reshaping anything — so the jitted decode step never
recompiles. Per-slot positions (`state["t"]` as an int32 vector) are what
let rows sit at different sequence offsets (models/model.py per-slot ops).

Host-side metadata (which request owns which row, its next input token, how
many tokens it still owes) stays in numpy; only the cache tensors live in
jax. The GO cache rows ride along with the KV rows: `write_decode_slot`
splats a single-request prefill (KV + per-layer GO entries) into the row,
`init_decode_slot` clears it at retirement (scores back to -inf) so a stale
expert-choice cache can never leak into the next occupant.

PAGED mode (`paged=True`) replaces the dense per-slot KV rows with a shared
page pool: `k_pages`/`v_pages` hold `num_pages` fixed-size token blocks and
each slot carries a block table of physical page ids (0 = null page). The
host-side `PageAllocator` (serving/paging.py) reserves each request's
worst-case page count at admission (deadlock freedom) but hands pages out
lazily — `grow_active()` assigns one page as a slot's sequence crosses a
page boundary, right before the decode tick that writes it. The PERSISTENT
KV residency then caps out at `num_pages * page_size` tokens regardless of
num_slots x max_tokens, which is what lets the paged engine run strictly
more concurrent streams than the dense one on the same cache budget. (The
BANDWIDTH win rides on top: the Pallas paged-attention kernel,
kernels/paged_attn.py, walks the block table directly so per-tick traffic
scales with live pages; the gather fallback re-materializes a TRANSIENT
dense-layout K/V per layer per tick.) GO rows stay slot-resident (they are
[E, k]-shaped, not sequence-shaped); their score reset to -inf happens on
the allocator's free path at retirement. A latent-attention model's pool
holds one store of latent pages (`c_pages`) in place of K and V; every
page move (admission, release, scrub, preemption) goes over whichever
stores the state holds (models/model.py::page_stores).

With a `mesh`, the pool's tensors are laid out by the rule-based sharder
(`launch/sharding.py::serve_state_shardings`): slot rows over the
data-parallel axes, KV sequence / GO expert dims over "model" (paged: the
page dim over data-parallel, then the page interior over "model" on the
gather path or kv heads over "model" on the kernel path — the kernel
stages whole pages; block tables replicated). Slot writes and resets land on the sharded arrays in place;
after each the state is pinned back to the canonical shardings so the
jitted decode step never sees a drifted layout (sharding drift means silent
recompiles).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant as Q
from repro.models.model import (PAGE_STORES, init_decode_slot,
                                init_decode_state, page_stores,
                                paged_supported, write_decode_slot)
from repro.serving.paging import PageAllocator, pages_for_tokens
from repro.serving.scheduler import Request

# Module-level jits: the slot index is traced, so each op compiles once per
# pool SHAPE — shared across every engine/pool instance of that shape (the
# throughput benchmark spins up one engine per slot count).
_write_slot = jax.jit(write_decode_slot)
_reset_slot = jax.jit(init_decode_slot)
# Preemption-resume scatter: put a snapshot's live pages back into the page
# store at freshly-allocated physical ids. Compiles once per (store shape,
# live-page count) — page counts are small integers, so the cache stays tiny.
_scatter_pages = jax.jit(
    lambda store, ids, pages: store.at[:, ids].set(pages.astype(store.dtype)))


class SlotPool:
    """Fixed-width pool of per-request decode-cache rows."""

    def __init__(self, cfg, num_slots: int, max_tokens: int,
                 extras: dict | None = None, mesh=None, *,
                 paged: bool = False, page_size: int = 16,
                 num_pages: int | None = None):
        self.cfg = cfg
        self.num_slots = num_slots
        self.max_tokens = max_tokens
        self.mesh = mesh
        self.paged = bool(paged)
        self.page_size = page_size
        self.num_pages = None
        Q.validate_kv_quant(getattr(cfg, "kv_quant", "none"))
        self.quant = cfg.kv_quant != "none"
        self.dequant_max_abs_err = 0.0
        if self.quant and not self.paged:
            # quantized decode state is page-granular by construction —
            # there is no per-page scale to hang off a dense KV row
            raise ValueError(
                f"kv_quant={cfg.kv_quant!r} requires a paged pool (scale "
                "granularity IS page granularity) — enable paging "
                "(paged=True / REPRO_FORCE_PAGED) or set kv_quant='none'")
        if self.paged:
            if not paged_supported(cfg):
                raise ValueError(
                    "paged pool is attention-family only "
                    f"(block={cfg.block!r})")
            if max_tokens % page_size:
                raise ValueError(f"max_tokens={max_tokens} must be a "
                                 f"multiple of page_size={page_size}")
            if self.quant and page_size % 8:
                raise ValueError(
                    f"kv_quant={cfg.kv_quant!r} needs page_size divisible "
                    f"by 8 (int8 pages stage through the paged-attention "
                    f"kernel in 8-row sublane granules); got "
                    f"page_size={page_size}")
            # default: same token capacity as the dense pool, plus the null
            # page — paging then costs nothing and saves whatever requests
            # don't use. A smaller num_pages SIMULATES a tighter HBM budget.
            if num_pages is None:
                num_pages = num_slots * (max_tokens // page_size) + 1
            if mesh is not None:
                # the page dim shards over the data-parallel axes
                # (launch/sharding.py) only when it divides them — round up
                # so the pool actually SHARDS instead of silently
                # replicating the whole page store on every dp replica
                # (which would invert the HBM win the pool exists for)
                from repro.launch.mesh import axis_size, dp_axes
                dpn = 1
                for a in dp_axes(mesh):
                    dpn *= axis_size(mesh, a)
                num_pages += -num_pages % dpn
            self.num_pages = num_pages
            self.alloc = PageAllocator(num_pages, page_size,
                                       max_tokens=max_tokens)
            # host mirror of the device block tables ([B, P] int32)
            self.block_table = np.zeros(
                (num_slots, max_tokens // page_size), np.int32)
            self._bt_dirty = False
        # Per-request cross-attn memory arrives batch-1 via each prefill and
        # is splatted in by write_decode_slot — the pool itself always inits
        # the default (zero, [num_slots, ...]) memory rows.
        pool_extras = {k: v for k, v in (extras or {}).items()
                       if k != "memory"}
        self.state = init_decode_state(
            cfg, num_slots, max_tokens, pool_extras, per_slot_t=True,
            paged=(self.num_pages, page_size) if self.paged else None)
        self.shardings = None
        if mesh is not None:
            from repro.launch.sharding import serve_state_shardings
            self.shardings = serve_state_shardings(
                cfg, mesh, num_slots, max_tokens, pool_extras,
                paged=(self.num_pages, page_size) if self.paged else None)
            self.state = self._pin(self.state)
        # host-side slot metadata
        self.owner: list[Request | None] = [None] * num_slots
        self.pending = np.zeros(num_slots, np.int32)    # next input token
        self.remaining = np.zeros(num_slots, np.int64)  # tokens still owed
        self.t_host = np.zeros(num_slots, np.int64)     # next decode position
        self.admitted_total = 0
        # per-slot sampling state (temperature <= 0 -> greedy row)
        self.temps = np.zeros(num_slots, np.float32)
        self.top_ps = np.ones(num_slots, np.float32)
        self.keys = np.zeros((num_slots, 2), np.uint32)  # PRNG key per slot

    def _pin(self, state: dict) -> dict:
        """Reshard `state` onto the canonical pool layout (no-op without a
        mesh)."""
        if self.shardings is None:
            return state
        return jax.device_put(state, self.shardings)

    # ---------------------------------------------------------------- queries

    def free_slots(self) -> list[int]:
        return [i for i, o in enumerate(self.owner) if o is None]

    def num_active(self) -> int:
        return self.num_slots - len(self.free_slots())

    def any_active(self) -> bool:
        return any(o is not None for o in self.owner)

    def active_mask(self) -> np.ndarray:
        return np.array([o is not None for o in self.owner], bool)

    def pages_needed(self, req: Request) -> int:
        """Worst-case page count: every position the request may ever write
        (prompt + full generation)."""
        return pages_for_tokens(req.prompt_len + req.max_new_tokens,
                                self.page_size)

    def can_admit(self, req: Request) -> bool:
        """The scheduler's admission gate: a dense pool only needs the free
        slot the engine already found; a paged pool additionally needs the
        request's worst-case page count to be reservable."""
        return (not self.paged) or self.alloc.can_reserve(
            self.pages_needed(req))

    # -------------------------------------------------------------- lifecycle

    def reserve_pages(self, req: Request) -> None:
        """Reserve a request's worst-case pages ahead of admission (chunked
        prefill claims its budget when the chunk run STARTS, so decode
        growth can never strand a half-prefilled prompt)."""
        if self.paged:
            self.alloc.reserve(req.request_id, self.pages_needed(req))

    def claim_chunk_pages(self, req: Request) -> np.ndarray:
        """Chunk-run page claim: reserve the request's worst case AND
        allocate the pages covering prompt + first decode write up front,
        so every prefill chunk scatters straight into the pool's pages.
        Returns the request's full block-table row (pass it back through
        `admit(page_row=)` when the run completes)."""
        assert self.paged, "chunk-run page claims are paged-pool only"
        self.reserve_pages(req)
        n0 = pages_for_tokens(req.prompt_len + 1, self.page_size)
        ids = self.alloc.alloc(req.request_id, n0)
        row = np.zeros(self.block_table.shape[1], np.int32)
        row[:n0] = ids
        return row

    def admit(self, slot: int, req: Request, slot_state: dict,
              first_token: int, key=None, *, page_row=None) -> None:
        """Install a prefilled request into a free row: write its KV + GO
        cache entries and position in place, arm its first decode input.
        `key` is the slot's sampling PRNG state (already advanced past the
        first token) for temperature > 0 requests. Paged pools allocate the
        pages covering the prompt and the first decode write here; later
        pages arrive lazily via grow_active(). A chunked-prefill run that
        already claimed its pages (claim_chunk_pages) passes its block-table
        row via `page_row` — its KV sits in the pool's pages, so the write
        splats only position/GO state."""
        assert self.owner[slot] is None, f"slot {slot} is occupied"
        if self.paged:
            if page_row is None:
                self.reserve_pages(req)
                n0 = pages_for_tokens(req.prompt_len + 1, self.page_size)
                ids = self.alloc.alloc(req.request_id, n0)
                row = np.zeros(self.block_table.shape[1], np.int32)
                row[:n0] = ids
            else:
                row = np.asarray(page_row, np.int32)
            self.block_table[slot] = row
            self.state = self._pin(_write_slot(
                self.state, slot, slot_state, jnp.asarray(row)))
            if self.quant:
                self._note_dequant_err(slot_state)
        else:
            self.state = self._pin(_write_slot(self.state, slot, slot_state))
        self.owner[slot] = req
        self.pending[slot] = first_token
        self.remaining[slot] = req.max_new_tokens - 1   # first token emitted
        self.t_host[slot] = req.prompt_len
        self.admitted_total += 1
        self.temps[slot] = req.temperature
        self.top_ps[slot] = req.top_p
        self.keys[slot] = 0 if key is None else np.asarray(key, np.uint32)
        req.slot = slot

    def _note_dequant_err(self, slot_state: dict) -> None:
        """Track the observed quantize->dequantize round-trip error of an
        admission's splatted state (running max, surfaced via engine
        stats()). Recomputes the splat quantization — a pure function of
        the prefill values — so the audit needs no fp32 shadow pool."""
        for srck in ("k", "v"):
            if srck not in slot_state:
                continue
            src = jnp.asarray(slot_state[srck])[:, 0].astype(jnp.float32)
            L = src.shape[0]
            pages = src.reshape(L, -1, self.page_size, *src.shape[2:])
            qp, sc = Q.quantize_pages(pages)
            err = float(jnp.abs(pages - Q.dequantize_pages(qp, sc)).max())
            self.dequant_max_abs_err = max(self.dequant_max_abs_err, err)
        go = slot_state.get("go")
        if go is not None:
            out = jnp.asarray(go.outputs).astype(jnp.float32)
            qo, so = Q.quantize_rows(out)
            err = float(jnp.abs(out - Q.dequantize_rows(qo, so)).max())
            self.dequant_max_abs_err = max(self.dequant_max_abs_err, err)

    # --------------------------------------------------------- prefix sharing

    def claim_prefix_ext_pages(self, req: Request, shared) -> np.ndarray:
        """Prefix-extension page claim: map the cached prefix's physical
        pages copy-on-write as the request's leading block-table entries,
        reserve its worst case, and allocate fresh pages for the rest of
        the prompt + first decode write. The caller prefills ONLY the
        non-shared remainder (prefill_chunk starting past the prefix) into
        the fresh pages; the shared pages are never written — the first
        write lands at position len(shared) * page_size or later."""
        assert self.paged, "prefix sharing is paged-pool only"
        rid = req.request_id
        shared = [int(p) for p in shared]
        self.alloc.share(rid, shared)
        self.reserve_pages(req)
        n0 = pages_for_tokens(req.prompt_len + 1, self.page_size)
        fresh = self.alloc.alloc(rid, n0 - len(shared))
        row = np.zeros(self.block_table.shape[1], np.int32)
        row[:len(shared)] = shared
        row[len(shared):n0] = fresh
        return row

    def admit_from_prefix(self, slot: int, req: Request, shared,
                          entry: dict, first_token: int, key=None) -> None:
        """Zero-prefill admission from a full-prompt prefix-index entry:
        block-table surgery in the style of `restore()`. The prompt's full
        pages map the donor's physical pages copy-on-write (`shared` —
        nothing moves on device); the tail positions past the last full
        page are scattered from the entry's host copy into the request's
        FIRST fresh page (they live in the donor's private page, which its
        decode overwrote); GO rows restore from the entry's snapshot (they
        are TopKUpdate history — not recomputable, the reason the entry
        carries them); the first decode input is the token the engine
        derived from the entry's cached prefill logits. The request writes
        only its fresh pages from here on, so the donor and every other
        sharer stay bit-identical."""
        assert self.paged and self.owner[slot] is None
        rid = req.request_id
        n_sh = len(shared)
        shared = [int(p) for p in shared]
        self.alloc.share(rid, shared)
        self.reserve_pages(req)
        n0 = pages_for_tokens(req.prompt_len + 1, self.page_size)
        fresh = self.alloc.alloc(rid, n0 - n_sh)
        row = np.zeros(self.block_table.shape[1], np.int32)
        row[:n_sh] = shared
        row[n_sh:n0] = fresh
        self.block_table[slot] = row
        tail = req.prompt_len - n_sh * self.page_size
        if tail:
            pid = int(fresh[0])
            self.state["k_pages"] = self.state["k_pages"].at[
                :, pid, :tail].set(jnp.asarray(entry["tail_k"]).astype(
                    self.state["k_pages"].dtype))
            self.state["v_pages"] = self.state["v_pages"].at[
                :, pid, :tail].set(jnp.asarray(entry["tail_v"]).astype(
                    self.state["v_pages"].dtype))
            if self.quant:
                # the tail page's scales travel with its int8 bytes — the
                # consumer's decode grows this page under the donor's
                # exact scale, so shared-prefix streams stay deterministic
                self.state["k_scales"] = self.state["k_scales"].at[
                    :, pid].set(jnp.asarray(entry["tail_ks"]))
                self.state["v_scales"] = self.state["v_scales"].at[
                    :, pid].set(jnp.asarray(entry["tail_vs"]))
        self.state["t"] = self.state["t"].at[slot].set(req.prompt_len)
        if "go" in self.state:
            self.state["go"] = jax.tree.map(
                lambda a, r: a.at[:, slot].set(jnp.asarray(r).astype(a.dtype)),
                self.state["go"], entry["go"])
        if "go_scales" in self.state:
            self.state["go_scales"] = self.state["go_scales"].at[
                :, slot].set(jnp.asarray(entry["go_scales"]))
        self._push_block_table()
        self.state = self._pin(self.state)
        self.owner[slot] = req
        self.pending[slot] = first_token
        self.remaining[slot] = req.max_new_tokens - 1   # first token emitted
        self.t_host[slot] = req.prompt_len
        self.admitted_total += 1
        self.temps[slot] = req.temperature
        self.top_ps[slot] = req.top_p
        self.keys[slot] = 0 if key is None else np.asarray(key, np.uint32)
        req.slot = slot

    def grow_active(self) -> None:
        """Paged pools: make sure every active slot owns the page its NEXT
        decode write lands in (position t_host). Reservations guarantee the
        grow succeeds; call once per engine tick, before the decode step."""
        if not self.paged:
            return
        for slot, req in enumerate(self.owner):
            if req is None:
                continue
            idx = int(self.t_host[slot]) // self.page_size
            if idx < self.block_table.shape[1] and \
                    self.block_table[slot, idx] == 0:
                self.block_table[slot, idx] = self.alloc.grow(req.request_id)
                self._bt_dirty = True
        if self._bt_dirty:
            self._push_block_table()

    def _push_block_table(self) -> None:
        bt = jnp.asarray(self.block_table)
        if self.shardings is not None:
            bt = jax.device_put(bt, self.shardings["block_table"])
        self.state["block_table"] = bt
        self._bt_dirty = False

    def note_decoded(self) -> None:
        """Advance the host mirror of each active slot's position after a
        decode tick (keeps grow_active off the device)."""
        for slot, req in enumerate(self.owner):
            if req is not None:
                self.t_host[slot] += 1

    def release_pages(self, rid: int) -> None:
        """Drop every page reference `rid` holds (request retirement, chunk
        cancellation, prefix-index eviction all route here) and zero the
        scrub-marked pages among those actually RELEASED — shared pages
        survive until their last owner frees them, so the scrub fires
        exactly on last free."""
        if self.paged:
            self.scrub_released(self.alloc.free(rid))

    def scrub_released(self, released) -> None:
        """Zero the deferred-scrub pages among just-released `released`
        (PR 7's NaN quarantine: 0 * NaN is NaN, so a poisoned page must be
        cleaned before any future stream can map it — but not before its
        LAST reference drops, other owners may still be reading it)."""
        if not self.paged or not released:
            return
        changed = False
        if self.quant:
            # EVERY released page returns with zeroed scales (not just the
            # scrub-marked ones): the rescale-on-write contract makes a
            # page's contents a pure function of the tokens written to it
            # ONLY if it starts from scale 0 — an inherited amax would
            # quantize a reused page differently than a fresh one, breaking
            # deterministic preempt/resume parity. (The first write into a
            # scale-0 page also rescales the stale int8 bytes by factor 0,
            # so old contents never survive reuse.)
            ids = jnp.asarray(sorted(released), jnp.int32)
            self.state["k_scales"] = self.state["k_scales"].at[:, ids].set(0)
            self.state["v_scales"] = self.state["v_scales"].at[:, ids].set(0)
            changed = True
        dirty = self.alloc.pop_dirty(released)
        if dirty:
            ids = jnp.asarray(dirty, jnp.int32)
            for key in page_stores(self.state):
                self.state[key] = self.state[key].at[:, ids].set(0)
            changed = True
        if changed:
            self.state = self._pin(self.state)

    def retire(self, slot: int, *, scrub: bool = False) -> Request:
        """Free a row: clear its caches (GO scores to -inf) and return the
        finished request. The row is immediately reusable. Paged pools
        return the slot's pages to the allocator on this same path — the
        page CONTENTS are normally left as-is (finite garbage is harmless:
        stale positions are score-masked, and 0-weighted FINITE values
        vanish from the attention sum). `scrub=True` marks the pages for a
        zero-on-last-free scrub — required when quarantining a NON-FINITE
        slot, because 0 * NaN is NaN: a poisoned page handed to a future
        stream would leak straight through the mask on the value side.
        (Marked pages still shared with live owners are zeroed when their
        final reference drops; only the slot's PRIVATE pages can actually
        carry NaN — poison_slot forks shared pages before writing.)"""
        req = self.owner[slot]
        assert req is not None, f"slot {slot} is already free"
        if self.paged:
            if scrub:
                self.alloc.mark_scrub(req.request_id)
            self.release_pages(req.request_id)
            self.block_table[slot] = 0
        self.state = self._pin(_reset_slot(self.state, slot))
        self.owner[slot] = None
        self.pending[slot] = 0
        self.remaining[slot] = 0
        self.t_host[slot] = 0
        self.temps[slot] = 0.0
        self.top_ps[slot] = 1.0
        self.keys[slot] = 0
        return req

    # ------------------------------------------------------------- preemption

    def snapshot(self, slot: int) -> dict:
        """Host-side eviction snapshot of an active PAGED slot: the slot's
        LIVE KV pages (device -> host), its GO rows, and its decode cursor /
        sampling state. Restoring this via `restore()` is bit-identical to
        never evicting — unlike recomputing the KV by re-prefilling, which
        is NOT bit-exact (full-sequence prefill matmuls differ bitwise from
        incremental decode ones) and cannot reproduce an expert-choice GO
        cache at all (the decode-time GO rows are TopKUpdate history over
        per-step capacities, not a function of re-routing the sequence)."""
        assert self.paged, "preemption snapshots are paged-pool only"
        req = self.owner[slot]
        assert req is not None, f"slot {slot} is free"
        row = self.block_table[slot]
        n = int((row != 0).sum())
        assert (row[:n] != 0).all(), "block table is not a contiguous prefix"
        ids = row[:n].copy()
        snap = {
            "t": int(self.t_host[slot]),
            "pending": int(self.pending[slot]),
            "remaining": int(self.remaining[slot]),
            "temp": float(self.temps[slot]),
            "top_p": float(self.top_ps[slot]),
            "key": self.keys[slot].copy(),
            "n_pages": n,
        }
        for key in page_stores(self.state):
            snap[PAGE_STORES[key]] = np.asarray(self.state[key][:, ids])
        if self.quant:
            snap["ks"] = np.asarray(self.state["k_scales"][:, ids])
            snap["vs"] = np.asarray(self.state["v_scales"][:, ids])
        if "go" in self.state:
            snap["go"] = jax.tree.map(lambda a: np.asarray(a[:, slot]),
                                      self.state["go"])
        if "go_scales" in self.state:
            snap["go_scales"] = np.asarray(self.state["go_scales"][:, slot])
        return snap

    def pages_for_resume(self, snap: dict) -> int:
        """Worst-case page count to finish a snapshotted stream: every
        position it has written plus every token it still owes."""
        return pages_for_tokens(snap["t"] + snap["remaining"], self.page_size)

    def can_resume(self, snap: dict) -> bool:
        return self.alloc.can_reserve(self.pages_for_resume(snap))

    def restore(self, slot: int, req: Request, snap: dict) -> None:
        """Re-admit a preempted request from its eviction snapshot: reserve
        its remaining worst case, allocate fresh physical pages for the live
        prefix, scatter the snapshot back in, and rebuild the slot's block
        table + GO rows + cursor — block-table surgery, no recompute."""
        assert self.paged and self.owner[slot] is None
        rid = req.request_id
        self.alloc.reserve(rid, self.pages_for_resume(snap))
        ids = self.alloc.alloc(rid, snap["n_pages"])
        row = np.zeros(self.block_table.shape[1], np.int32)
        row[:len(ids)] = ids
        self.block_table[slot] = row
        jids = jnp.asarray(ids, jnp.int32)
        for key in page_stores(self.state):
            self.state[key] = _scatter_pages(
                self.state[key], jids, jnp.asarray(snap[PAGE_STORES[key]]))
        if self.quant:
            # int8 pages restore verbatim WITH their scales — resume is
            # bit-identical to never evicting, same as the fp32 pool
            self.state["k_scales"] = _scatter_pages(
                self.state["k_scales"], jids, jnp.asarray(snap["ks"]))
            self.state["v_scales"] = _scatter_pages(
                self.state["v_scales"], jids, jnp.asarray(snap["vs"]))
        self.state["t"] = self.state["t"].at[slot].set(snap["t"])
        if "go" in self.state:
            self.state["go"] = jax.tree.map(
                lambda a, r: a.at[:, slot].set(jnp.asarray(r).astype(a.dtype)),
                self.state["go"], snap["go"])
        if "go_scales" in self.state:
            self.state["go_scales"] = self.state["go_scales"].at[
                :, slot].set(jnp.asarray(snap["go_scales"]))
        self._push_block_table()
        self.state = self._pin(self.state)
        self.owner[slot] = req
        self.pending[slot] = snap["pending"]
        self.remaining[slot] = snap["remaining"]
        self.t_host[slot] = snap["t"]
        self.temps[slot] = snap["temp"]
        self.top_ps[slot] = snap["top_p"]
        self.keys[slot] = snap["key"]
        self.admitted_total += 1
        req.slot = slot

    # --------------------------------------------------------- fault injection

    def poison_slot(self, slot: int) -> None:
        """Chaos hook: corrupt one slot's decode state with NaN (its most
        recently written KV position — always inside the attention window)
        so the NEXT decode tick produces non-finite logits for that row and
        ONLY that row (every batched op is row-wise independent). The engine
        must quarantine the slot without touching its cohabitants."""
        assert self.owner[slot] is not None, f"slot {slot} is free"
        t = max(0, int(self.t_host[slot]) - 1)
        if self.paged:
            idx = t // self.page_size
            page = int(self.block_table[slot, idx])
            if self.alloc.refcount(page) > 1:
                # the target position sits in a SHARED prefix page (this is
                # the divergent write the COW contract forbids in place) —
                # fork a private copy first so the donor and every other
                # sharer keep their clean state. No spare page beyond the
                # in-flight reservations -> skip this fault injection;
                # stealing a promised page would break deadlock freedom.
                if not self.alloc.can_reserve(1):
                    return
                new = self.alloc.fork(
                    self.owner[slot].request_id, page)
                for key in page_stores(self.state):
                    self.state[key] = self.state[key].at[:, new].set(
                        self.state[key][:, page])
                if self.quant:
                    # a forked int8 page is only meaningful WITH its scale
                    self.state["k_scales"] = self.state["k_scales"].at[
                        :, new].set(self.state["k_scales"][:, page])
                    self.state["v_scales"] = self.state["v_scales"].at[
                        :, new].set(self.state["v_scales"][:, page])
                self.block_table[slot, idx] = new
                self._push_block_table()
                page = new
            off = t % self.page_size
            if self.quant:
                # NaN cannot be stored in int8 pages — poison the page's
                # SCALE instead: dequant makes the whole page NaN, which
                # still reaches the next tick's logits for this row only.
                # Quarantine scrubs the scale back to 0 with the page.
                self.state["k_scales"] = \
                    self.state["k_scales"].at[:, page].set(jnp.nan)
            else:
                key = page_stores(self.state)[0]
                self.state[key] = \
                    self.state[key].at[:, page, off].set(jnp.nan)
        elif "k" in self.state or "c" in self.state:
            key = "k" if "k" in self.state else "c"
            self.state[key] = self.state[key].at[:, slot, t].set(jnp.nan)
        else:
            # recurrent archs: no KV rows — poison the slot's carried state
            # (batch axes per key match init_decode_slot: ssm/slstm -> 1,
            # mlstm -> 2); integer leaves are left alone
            def rot(a, batch_axis):
                if not jnp.issubdtype(a.dtype, jnp.floating):
                    return a
                idx = (slice(None),) * batch_axis + (slot,)
                return a.at[idx].set(jnp.nan)
            for key, ax in (("ssm", 1), ("mlstm", 2), ("slstm", 1)):
                if key in self.state:
                    self.state[key] = jax.tree.map(
                        lambda a: rot(a, ax), self.state[key])
        self.state = self._pin(self.state)

    # -------------------------------------------------------------- invariants

    def audit(self) -> None:
        """Pool/slot invariant sweep (REPRO_AUDIT=1 runs it every engine
        tick): allocator consistency, block tables as contiguous prefixes
        matching exactly the allocator's ownership, host/device position
        mirrors in sync, live metadata sane, freed slots fully cleared."""
        if self.paged:
            self.alloc.check()
        dev_t = np.asarray(self.state["t"])
        for slot, req in enumerate(self.owner):
            if req is None:
                assert self.remaining[slot] == 0 and self.t_host[slot] == 0, \
                    f"freed slot {slot} has stale metadata"
                assert dev_t[slot] == 0, \
                    f"freed slot {slot}: device t={dev_t[slot]} not reset"
                if self.paged:
                    assert (self.block_table[slot] == 0).all(), \
                        f"freed slot {slot} still maps pages"
                continue
            assert self.remaining[slot] > 0, \
                f"active slot {slot} owes no tokens"
            t = int(self.t_host[slot])
            assert 0 < t <= self.max_tokens, f"slot {slot}: t={t} out of range"
            assert dev_t[slot] == t, \
                f"slot {slot}: device t={dev_t[slot]} != host t={t}"
            if self.paged:
                row = self.block_table[slot]
                n = int((row != 0).sum())
                assert (row[:n] != 0).all() and (row[n:] == 0).all(), \
                    f"slot {slot}: block table not a contiguous prefix"
                owned = self.alloc.owned(req.request_id)
                assert set(row[:n].tolist()) == set(owned), \
                    f"slot {slot}: block table != allocator ownership"
                assert n >= pages_for_tokens(t, self.page_size), \
                    f"slot {slot}: {n} pages cannot back {t} positions"
        if self.quant:
            # scale hygiene: free pages must carry scale 0 (the
            # rescale-on-write determinism contract — see core/quant.py),
            # and no scale may be inf. NaN is tolerated on LIVE pages only:
            # it is the deliberate poison_slot fault on its way to the
            # engine's quarantine sweep.
            live = set(self.alloc.refcounts())
            ks = np.asarray(self.state["k_scales"])
            vs = np.asarray(self.state["v_scales"])
            free = sorted(set(range(1, self.num_pages)) - live)
            for name, s in (("k_scales", ks), ("v_scales", vs)):
                assert not np.isinf(s).any(), f"{name} has inf entries"
                if free:
                    fs = s[:, free]
                    assert (fs == 0).all(), \
                        f"{name}: freed pages carry non-zero scales " \
                        f"(pages {free[:8]}...) — scrub_released must zero " \
                        f"scales on every release"
            if "go_scales" in self.state:
                # freed slots' GO rows still flow through the masked decode
                # math each tick (exactly like the fp32 pool's — overwritten
                # at the next admission), so only finiteness is asserted
                gs = np.asarray(self.state["go_scales"])
                assert np.isfinite(gs).all(), \
                    "go_scales has non-finite entries"
