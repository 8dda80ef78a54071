"""Admission / retirement scheduling for the continuous-batching engine.

Host-side only (numpy, no jax): the scheduler decides WHICH request enters
the pool next; the pool/engine decide WHERE (free slot / which pages) and do
the device work. Policy knobs:

  max_slots   pool width — at most this many requests in flight at once
  max_tokens  pool sequence capacity — prompt + generation of every request
              must fit (enforced at submit; nothing is silently truncated)
  max_queue   optional backlog bound (0 = unbounded) over queued AND
              not-yet-arrived trace requests; submit raises when the backlog
              is full, the serving analogue of load-shedding

Admission order is a PRIORITY HEAP: requests carry `priority` (int, lower =
admitted earlier, 0 default) and the heap breaks ties by submission order —
FIFO within a priority level, so equal-priority requests can never starve
each other (pinned in tests/test_serving.py). This is the first step toward
Sieve-style expert-aware admission: a cost model only has to assign
priorities, the ordering machinery is already here.

Admission can be gated by a `can_admit` predicate (the paged pool's "are
enough pages reservable?" question). The gate applies to the HEAD of the
heap only — a blocked head blocks everything behind it rather than letting
smaller requests overtake, which keeps the order starvation-free.

Requests may carry an `arrival_step`: the trace-replay hook used by the
staggered-arrival tests and the Poisson-trace throughput benchmark. Such a
request stays in the `pending` list until the engine's step counter reaches
its arrival step, then joins the admission heap (keyed by its SUBMIT order,
so same-tick arrivals stay FIFO).

LIFECYCLE: every request carries a typed `status` and ends in exactly one
terminal state — DONE (EOS/length), TIMEOUT (deadline_s from submission or
max_wall_s from first admission exceeded), CANCELLED (engine.cancel), or
FAILED (non-finite logits quarantined by the engine). PREEMPTED is the one
non-terminal excursion out of ACTIVE: a page-pressure eviction parks the
request back in this heap (requeue — it keeps its original submit order, so
it resumes at the head of its priority class) until its pages are
reservable again.
"""
from __future__ import annotations

import enum
import heapq
import itertools
from dataclasses import dataclass, field

import numpy as np


class RequestStatus(str, enum.Enum):
    """Request lifecycle states. str-mixin so `status == "DONE"` works."""

    QUEUED = "QUEUED"          # waiting for admission (incl. trace-deferred)
    ACTIVE = "ACTIVE"          # occupying a slot (prefilling or decoding)
    PREEMPTED = "PREEMPTED"    # evicted under page pressure, awaiting resume
    DONE = "DONE"              # terminal: EOS or length
    TIMEOUT = "TIMEOUT"        # terminal: deadline_s / max_wall_s exceeded
    CANCELLED = "CANCELLED"    # terminal: engine.cancel(rid)
    FAILED = "FAILED"          # terminal: quarantined (non-finite logits)


TERMINAL_STATUSES = frozenset({
    RequestStatus.DONE, RequestStatus.TIMEOUT,
    RequestStatus.CANCELLED, RequestStatus.FAILED,
})


class QueueFull(RuntimeError):
    """Typed backpressure signal: the admission backlog is at max_queue.
    Carries the observed depth so callers can shed load proportionally."""

    def __init__(self, depth: int, max_queue: int):
        self.depth = depth
        self.max_queue = max_queue
        super().__init__(
            f"admission queue full: depth {depth} >= max_queue {max_queue}")


class RequestTooLarge(ValueError):
    """Typed submit-time rejection: the request could never fit the pool
    (prompt + max_new_tokens over max_tokens, or over the paged pool's
    usable page count), so admitting it would stall the queue forever."""


@dataclass
class Request:
    """One generation request plus its lifecycle bookkeeping."""

    request_id: int
    prompt: np.ndarray               # [T] int32 token ids
    max_new_tokens: int
    eos_id: int | None = None
    extras: dict | None = None       # per-request cross-attn memory (vlm/audio)
    arrival_step: int = 0            # engine step at which the request arrives
    priority: int = 0                # admission class: lower = admitted first
    # --- sampling (temperature <= 0 -> greedy, the default) ---
    temperature: float = 0.0
    top_p: float = 1.0
    seed: int | None = None          # None -> derived from request_id
    # --- deadlines (None = unbounded) ---
    deadline_s: float | None = None  # wall budget from submission
    max_wall_s: float | None = None  # wall budget from FIRST admission
    # --- routing fingerprint (expert-aware admission; None = unknown) ---
    # bool [num_experts]: which experts this request's prompt is PREDICTED
    # to touch. Filled by the engine (layer-0 gate probe at submit, refined
    # from the observed GO rows at admission). Purely a scheduling hint —
    # never consulted on any compute path, so a wrong prediction costs
    # batch composition quality, not correctness.
    expert_sig: object = None

    # --- filled in by the scheduler ---
    # times an ExpertAwareScheduler's cost model admitted a different
    # same-priority candidate past this one; at max_skips the request is
    # force-admitted regardless of score (the starvation bound)
    times_skipped: int = 0

    # --- filled in by the engine ---
    status: RequestStatus = RequestStatus.QUEUED
    fail_reason: str | None = None   # set on FAILED/TIMEOUT/CANCELLED
    # Host clock stamps (time.monotonic): arrival_time <= start_time <=
    # admit_time. start_time marks when the request leaves the queue (its
    # slot claimed for a one-shot prefill, or its chunked prefill begun);
    # admit_time marks its first token (the end of its prefill). A journal
    # recovery re-anchors all of them at the recovery's clock.
    arrival_time: float = 0.0        # wall-clock when it joined the queue
    submit_time: float = 0.0         # wall-clock at submit (deadline_s anchor)
    start_time: float = 0.0          # wall-clock when it left the queue
    admit_time: float = 0.0          # wall-clock at FIRST admission
    admit_step: int = -1
    finish_step: int = -1
    finish_time: float = 0.0
    slot: int = -1                   # slot it was admitted into
    seq: int = -1                    # scheduler submit order (heap tie-break)
    preemptions: int = 0             # times evicted under page pressure
    tokens: list[int] = field(default_factory=list)

    @property
    def latency_s(self) -> float:
        return self.finish_time - self.arrival_time

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])

    def expired(self, now: float) -> bool:
        """Has either wall budget run out? deadline_s counts from submit
        (queue wait included); max_wall_s counts from first admission and
        keeps counting across preemptions (the request is still holding a
        snapshot, i.e. engine memory)."""
        if self.deadline_s is not None and \
                now - self.submit_time > self.deadline_s:
            return True
        if self.max_wall_s is not None and self.admit_time > 0 and \
                now - self.admit_time > self.max_wall_s:
            return True
        return False


class FIFOScheduler:
    """Priority-heap admission (FIFO within a level) with the max-slots /
    max-tokens policy. The historical name survives because priority 0 is
    the default — an all-default workload IS a FIFO queue."""

    def __init__(self, max_slots: int, max_tokens: int, max_queue: int = 0):
        self.max_slots = max_slots
        self.max_tokens = max_tokens
        self.max_queue = max_queue
        self.queue: list[tuple[int, int, Request]] = []      # (prio, seq, req)
        self._pending: list[tuple[int, int, Request]] = []   # arrival-step heap
        self._seq = itertools.count()                        # submit order

    # ------------------------------------------------------------- submission

    def submit(self, req: Request, *, now_step: int = 0) -> None:
        """Queue a request (immediately, or at its arrival_step if later).
        Raises typed rejections: RequestTooLarge for a request that could
        never fit the pool, QueueFull (carrying the depth) at max_queue."""
        need = req.prompt_len + req.max_new_tokens
        if need > self.max_tokens:
            raise RequestTooLarge(
                f"request {req.request_id}: prompt({req.prompt_len}) + "
                f"max_new_tokens({req.max_new_tokens}) = {need} exceeds the "
                f"pool's max_tokens={self.max_tokens}")
        backlog = len(self.queue) + len(self._pending)
        if self.max_queue and backlog >= self.max_queue:
            raise QueueFull(backlog, self.max_queue)
        req.seq = next(self._seq)
        req.status = RequestStatus.QUEUED
        if req.arrival_step > now_step:
            heapq.heappush(self._pending, (req.arrival_step, req.seq, req))
            return
        heapq.heappush(self.queue, (req.priority, req.seq, req))

    def requeue(self, req: Request) -> None:
        """Put a PREEMPTED request back in the admission heap under its
        ORIGINAL submit order: it resumes ahead of everything submitted
        after it in its priority class (no progress lost to overtaking).
        Bypasses max_queue — the request was already admitted once, so
        bouncing it now would turn backpressure into data loss."""
        assert req.seq >= 0, "requeue() is for previously-submitted requests"
        heapq.heappush(self.queue, (req.priority, req.seq, req))

    def poll(self, step: int) -> list[Request]:
        """Move trace-replay requests whose arrival step has come into the
        admission heap; returns the newly arrived requests."""
        arrived = []
        while self._pending and self._pending[0][0] <= step:
            _, seq, req = heapq.heappop(self._pending)
            heapq.heappush(self.queue, (req.priority, seq, req))
            arrived.append(req)
        return arrived

    # -------------------------------------------------------------- admission

    def next_admission(self, num_active: int,
                       can_admit=None) -> Request | None:
        """Pop the next request to admit, or None (empty heap, the pool is
        already at max_slots, or `can_admit` rejects the head — e.g. the
        paged pool cannot reserve its worst-case page count yet)."""
        if not self.queue or num_active >= self.max_slots:
            return None
        head = self.queue[0][2]
        if can_admit is not None and not can_admit(head):
            return None
        return heapq.heappop(self.queue)[2]

    # ------------------------------------------------------ removal / expiry

    def remove(self, rid: int) -> Request | None:
        """Pull a request out of the admission heap / pending trace list by
        id (cancellation before admission). Returns it, or None if it is
        not queued here."""
        for heap in (self.queue, self._pending):
            for i, (_, _, req) in enumerate(heap):
                if req.request_id == rid:
                    heap.pop(i)
                    heapq.heapify(heap)
                    return req
        return None

    def expire(self, now: float) -> list[Request]:
        """Drop every queued/pending request whose wall budget has run out
        (Request.expired) and return them; the engine marks them TIMEOUT.
        Covers PREEMPTED requests parked here awaiting resume."""
        out = [req for _, _, req in self.queue if req.expired(now)]
        out += [req for _, _, req in self._pending if req.expired(now)]
        if out:
            gone = {r.request_id for r in out}
            self.queue = [e for e in self.queue
                          if e[2].request_id not in gone]
            heapq.heapify(self.queue)
            self._pending = [e for e in self._pending
                             if e[2].request_id not in gone]
            heapq.heapify(self._pending)
        return out

    def has_pending(self) -> bool:
        return bool(self.queue) or bool(self._pending)

    def next_arrival_step(self) -> int | None:
        """Earliest future arrival step (None when no trace-replay requests
        remain) — lets an idle engine fast-forward its tick counter."""
        return self._pending[0][0] if self._pending else None


class ExpertAwareScheduler(FIFOScheduler):
    """Admission driven by a routing-overlap cost model instead of pure
    arrival order (Sieve-style: per-expert load EWMAs track expert
    popularity as it evolves; the HD-MoE insight that batch composition
    should key off OBSERVED routing).

    The objective is the planner's occupancy telemetry: a decode tick over
    requests that route to the same few experts packs those experts' tiles
    full, while a batch spread across many experts pays tile setup for
    mostly-empty lanes. So within the head priority class, admission picks
    the candidate whose predicted expert signature

      * overlaps most with the union of the ACTIVE batch's signatures
        (reuses experts the tick already pays for),
      * introduces fewest NEW experts, and
      * avoids hot experts (EWMA load — spreading arrivals away from
        recently-popular experts keeps per-expert queueing bounded as
        popularity drifts).

    STRICT PRIORITY is inherited unchanged: candidates come only from the
    head priority class, so a lower class never overtakes. STARVATION
    within the class is bounded by an explicit AGING CAP, not by the scan
    window (the window bounds the SCAN, not how often a candidate can be
    passed over — an old request with a disjoint signature could otherwise
    be skipped forever while overlapping same-priority arrivals keep
    coming): every time the cost model admits past a scanned candidate its
    `times_skipped` rises, and a candidate at `max_skips` is FORCE-ADMITTED
    (oldest such first) regardless of score. So any request is admitted
    after at most `max_skips` same-class admissions overtake it, no matter
    how the active set churns. Requests with no signature (dense prompts,
    probe disabled) score 0 — an all-None workload degenerates to EXACT
    FIFO order including head-blocking semantics, which is what keeps the
    existing test matrix green (ties break by submit order, so nothing is
    ever skipped and the aging cap never engages).

    Correctness-neutral by design: admission ORDER is the only output; the
    decode math of an admitted request is row-independent, so streams stay
    bit-identical to the FIFO path no matter how this reorders them."""

    def __init__(self, max_slots: int, max_tokens: int, max_queue: int = 0,
                 *, num_experts: int, ewma_alpha: float = 0.25,
                 window: int = 8, load_weight: float = 0.125,
                 max_skips: int = 16):
        super().__init__(max_slots, max_tokens, max_queue)
        self.num_experts = num_experts
        self.ewma_alpha = ewma_alpha
        self.window = window
        self.load_weight = load_weight
        self.max_skips = max_skips
        self.load = np.zeros(num_experts, np.float64)  # per-expert EWMA
        self._active_union = np.zeros(num_experts, bool)
        # the request the page gate rejected this tick (the preemption
        # machinery frees pages for THIS one, not the arrival-order head)
        self.last_blocked: Request | None = None

    # ------------------------------------------------------------ observation

    def observe(self, sig) -> None:
        """Fold one admitted request's observed/predicted routing into the
        per-expert load EWMAs (Sieve's evolving-popularity signal)."""
        if sig is None:
            return
        self.load *= 1.0 - self.ewma_alpha
        self.load[np.asarray(sig, bool)] += self.ewma_alpha

    def note_active(self, sigs) -> None:
        """Refresh the active batch's expert-union (engine calls this with
        the signatures of every slot owner before asking for admissions)."""
        u = np.zeros(self.num_experts, bool)
        for s in sigs:
            if s is not None:
                u |= np.asarray(s, bool)
        self._active_union = u

    # -------------------------------------------------------------- admission

    def score(self, req: Request) -> float:
        """Higher = admit sooner. 0 for unknown signatures so unscored
        requests neither jump nor yield within their class."""
        if req.expert_sig is None:
            return 0.0
        sig = np.asarray(req.expert_sig, bool)
        new = sig & ~self._active_union
        overlap = int((sig & self._active_union).sum())
        return overlap - int(new.sum()) - \
            self.load_weight * float(self.load[new].sum())

    def victim_bonus(self, sig, other_sigs) -> int:
        """Preemption cost model: how many experts does this victim touch
        that NO other active request needs? Evicting the request with the
        most unique experts shrinks the tick's expert set the most."""
        if sig is None:
            return 0
        others = np.zeros(self.num_experts, bool)
        for s in other_sigs:
            if s is not None:
                others |= np.asarray(s, bool)
        return int((np.asarray(sig, bool) & ~others).sum())

    def next_admission(self, num_active: int,
                       can_admit=None) -> Request | None:
        """Pick the best-scoring candidate among the first `window`
        same-priority entries at the head of the heap — unless a scanned
        candidate has already been passed over `max_skips` times, in which
        case the OLDEST such candidate is force-admitted (the starvation
        bound; skips only count when an admission actually happens, so a
        blocked tick ages nobody). The page gate applies to the CHOSEN
        candidate (its identity is remembered in `last_blocked` so
        preemption frees pages for it, not for the arrival-order head)."""
        self.last_blocked = None
        if not self.queue or num_active >= self.max_slots:
            return None
        head_prio = self.queue[0][0]
        cands = heapq.nsmallest(
            self.window, (e for e in self.queue if e[0] == head_prio))
        forced = [e for e in cands if e[2].times_skipped >= self.max_skips]
        best = min(forced) if forced else \
            min(cands, key=lambda e: (-self.score(e[2]), e[1]))
        req = best[2]
        if can_admit is not None and not can_admit(req):
            self.last_blocked = req
            return None
        for e in cands:
            if e is not best:
                e[2].times_skipped += 1
        req.times_skipped = 0
        self.queue.remove(best)
        heapq.heapify(self.queue)
        return req
