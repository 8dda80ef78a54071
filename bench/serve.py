"""The measured run of one serving cell: build the program's engine over
the benchmark's weights, warm every shape the traffic will use, offer the
traffic on the wall clock, and record what a client sees.

The client's view is taken after each `ServingEngine.step()`: every token
that appeared during the step is stamped with the host clock at the step's
end. Requests are timed from when they were due, so the generator's own
lateness counts against the engine. Per step the harness also keeps what the
device was asked to do (decode rows and their positions, prefill tokens),
derived from the tokens it saw, for the per-layer readers.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic as TR


class CompileClock:
    """Backend compiles (a compile, or a load from the persistent cache:
    JAX times both as one event) with their seconds and program names, and
    the cache's hits and misses, read from jax.monitoring events, each
    stamped with the host clock: (time, kind, seconds, program)."""

    def __init__(self):
        self.events: list[tuple[float, str, float, str]] = []
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, ev, secs, **kw):
        if ev == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.monotonic(), "compile", float(secs),
                                str(kw.get("fun_name", ""))))

    def _ev(self, ev, **_):
        kind = {"/jax/compilation_cache/cache_hits": "hit",
                "/jax/compilation_cache/cache_misses": "miss"}.get(ev)
        if kind:
            self.events.append((time.monotonic(), kind, 0.0, ""))

    def between(self, t0: float, t1: float) -> list:
        return [e for e in self.events if t0 <= e[0] < t1]


@dataclass
class Rec:
    """One request as the client sees it."""
    planned: TR.Planned
    due: float                       # host clock when it was due
    submitted: float = 0.0
    rid: int = -1
    times: list = field(default_factory=list)     # one stamp per token
    req: object = None               # the engine's Request, once seen
    rejected: str | None = None

    @property
    def prompt_len(self) -> int:
        return len(self.planned.prompt)


@dataclass
class Step:
    """One engine tick: host span, the decode rows' positions (the key the
    row wrote, so position + 1 keys attended), one-shot prefills by real
    token count, and chunk prefills as (start, valid, last chunk) — filled
    after the run from the tokens seen."""
    t0: float
    t1: float
    decode: list = field(default_factory=list)
    oneshot: list = field(default_factory=list)
    chunks: list = field(default_factory=list)


@dataclass
class Run:
    """Everything the end-to-end and per-layer readers read."""
    sizes: dict
    engine: dict
    peaks: dict           # one chip's
    seconds: float
    t_proc: float
    chips: int = 1        # the chips the cell runs over
    t_open: float = 0.0
    t_close: float = 0.0
    recs: list = field(default_factory=list)
    steps: list = field(default_factory=list)
    compiles: CompileClock | None = None
    trace_span: tuple | None = None  # host clock (start, stop) of the trace
    trace: object = None             # profile.Trace of the traced span
    lateness: list = field(default_factory=list)
    open_loop: bool = True

    def window_recs(self) -> list:
        """The requests the window answers for: those due in it under an
        open loop; under a backlog or a closed loop, whose requests are due
        as the engine frees up, those with a token in the window or still
        waiting at its close."""
        if self.open_loop:
            return [r for r in self.recs
                    if self.t_open <= r.due < self.t_close]
        return [r for r in self.recs if r.due < self.t_close and (
            not r.times or r.times[-1] >= self.t_open)]

    def ttfts(self) -> list:
        """Due to first token, for every request due in the window; one
        still waiting at the close counts the wait so far."""
        return [min(r.times[0] if r.times else self.t_close, self.t_close)
                - r.due for r in self.window_recs()]

    def itl_gaps(self) -> list:
        """Gaps between consecutive tokens of a request that end in the
        window, and for a stream still open at the close, the wait since
        its last token. A one-shot prefill's first token and the decode
        token of the same tick leave the engine in one step, so that gap
        cannot be seen from outside it and is not counted."""
        gaps = []
        for r in self.recs:
            ts = r.times
            gaps += [b - a for a, b in zip(ts, ts[1:])
                     if b > a and self.t_open <= b < self.t_close]
            if ts and len(ts) < r.planned.max_new and ts[-1] < self.t_close \
                    and r.rejected is None:
                gaps.append(self.t_close - ts[-1])
        return gaps

    def tokens_in_window(self) -> int:
        return sum(self.t_open <= t < self.t_close
                   for r in self.recs for t in r.times)

    def traced_steps(self) -> list:
        if self.trace_span is None:
            return []
        a, b = self.trace_span
        return [s for s in self.steps if a <= s.t0 and s.t1 <= b]


@contextlib.contextmanager
def span(name: str, on: bool):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    if not on:
        yield
        return
    with jax.profiler.TraceAnnotation(name):
        yield


def program_config(conf: dict):
    """The program's ModelConfig for a configuration file: the registry
    entry with the file's overrides (nested `moe` overrides replace fields
    of the MoE config)."""
    from repro.configs.registry import get_config
    prog = conf["program"]
    cfg = get_config(prog["registry"])
    over = dict(prog.get("overrides", {}))
    moe = over.pop("moe", None)
    if moe:
        over["moe"] = dataclasses.replace(cfg.moe, **moe)
    return cfg.with_overrides(**over)


def check_program_matches(cfg, sz: dict) -> None:
    """The program serves the model the configuration file states."""
    got = {"layers": cfg.num_layers, "d_model": cfg.d_model,
           "heads": cfg.num_heads, "kv_heads": cfg.num_kv_heads,
           "head_dim": cfg.resolved_head_dim(), "vocab": cfg.vocab_size,
           "experts": cfg.moe.num_experts, "top_k": cfg.moe.top_k,
           "d_expert": cfg.moe.d_expert,
           "shared_experts": cfg.moe.num_shared_experts,
           "rope_theta": cfg.rope_theta, "norm_eps": cfg.norm_eps,
           "dtype": cfg.dtype}
    want = {k: sz[k] for k in got}
    if got != want or not cfg.tie_embeddings:
        raise ValueError(f"program config {got} (tied "
                         f"{cfg.tie_embeddings}) != configuration {want}")
    # the program's model code has no multipliers and renormalises its top-k
    # router weights: a configuration runs with those values, as `assumed`
    runs = {"emb_mult": 1.0, "res_mult": 1.0,
            "attn_scale": got["head_dim"] ** -0.5, "logit_scale": 1.0,
            "norm_topk": True}
    if any(sz[k] != v for k, v in runs.items()):
        raise ValueError(f"the program runs {runs}; the configuration, "
                         f"{ {k: sz[k] for k in runs} }")


def check_chip_paths(eng) -> dict:
    """The engine resolved to the chip's kernels (grouped-GEMM MoE, paged
    attention kernel, no interpret mode) and its compiled decode tick holds
    a Mosaic kernel."""
    from repro.core.moe import resolve_backend
    from repro.kernels import paged_attn
    from repro.kernels.moe_gmm import default_interpret
    got = {"moe_backend": resolve_backend(eng.cfg.moe),
           "paged_attention": paged_attn.resolve_mode(eng.cfg),
           "interpret": default_interpret()}
    want = {"moe_backend": "pallas", "paged_attention": "kernel",
            "interpret": False}
    if got != want:
        raise RuntimeError(f"chip paths resolved to {got}, want {want}")
    hlo = lower_tick(eng).as_text()
    got["decode_tpu_custom_calls"] = hlo.count("tpu_custom_call")
    if not got["decode_tpu_custom_calls"]:
        raise RuntimeError("the decode tick holds no tpu_custom_call")
    return got


def lower_tick(eng):
    """The engine's decode tick, lowered as the engine runs it: in its mesh
    scope, where under a mesh each Mosaic kernel is put in a shard_map
    (kernels/moe_gmm.py) and outside which the tick does not lower."""
    from repro.serving import engine as ENG
    pool = eng.pool
    with eng._mesh_scope():
        return ENG._decode_step.lower(
            eng.params, pool.state, jnp.asarray(pool.pending),
            jnp.asarray(pool.active_mask()), eng.cfg)


def decode_hlo(eng) -> str:
    """The compiled decode tick's HLO text; the persistent compile cache
    holds it from the run."""
    return lower_tick(eng).compile().as_text()


def make_mesh(chips: int):
    """The program's serving mesh over the first `chips` devices: one
    replica, the model axis over every chip (experts split over it)."""
    from repro.launch.mesh import make_mesh as program_mesh
    return program_mesh((1, chips), ("data", "model"),
                        devices=jax.devices()[:chips])


def weight_shardings(shapes, cfg, mesh):
    """The program's serve-mode layout of the weights over `mesh`."""
    from repro.launch.sharding import param_shardings
    return param_shardings(shapes, cfg, mesh, mode="serve")


def make_engine(params, cfg, eng_spec: dict, mesh=None):
    from repro.serving import ServingEngine
    return ServingEngine(
        params, cfg, num_slots=eng_spec["slots"],
        max_tokens=eng_spec["max_tokens"], paged=True,
        page_size=eng_spec["page_size"], num_pages=eng_spec.get("num_pages"),
        prefill_chunk=eng_spec["prefill_chunk"], prompt_buckets=True,
        mesh=mesh)


def prefill_bucket(n: int, max_tokens: int) -> int:
    """The engine's one-shot prefill length for an n-token prompt."""
    b = 8
    while b < n:
        b *= 2
    return min(b, max_tokens)


def warm_up(eng, plan: TR.Plan, vocab: int) -> int:
    """Serve one 2-token request per prefill program the plan will use —
    each one-shot bucket, padded or filled exactly, and the chunk program
    when a prompt is chunked — so
    every program the window drives is compiled, or loaded from the cache,
    before it opens. Returns the number of warm-up requests."""
    chunk, mt = eng.prefill_chunk, eng.pool.max_tokens
    lens = sorted({len(r.prompt) for r in plan.requests})
    shapes = {}
    for n in lens:
        # a prompt that fills its bucket exactly compiles its own program
        b = prefill_bucket(n, mt)
        key = "chunk" if n > chunk else (b, b == n)
        shapes.setdefault(key, n)
    rng = np.random.default_rng(0)
    for n in shapes.values():
        eng.submit(rng.integers(0, vocab, size=n, dtype=np.int32), 2)
    while eng.has_work():
        eng.step()
    return len(shapes)


class Driver:
    """Offers a plan to the engine on the wall clock and records it."""

    def __init__(self, eng, plan: TR.Plan, run: Run, *, trace_s: float = 0.0,
                 trace_dir: str | None = None):
        self.eng, self.plan, self.run = eng, plan, run
        self.trace_s, self.trace_dir = trace_s, trace_dir
        self.by_rid: dict[int, Rec] = {}
        self.tracing = False

    def submit(self, p: TR.Planned, due: float) -> None:
        rec = Rec(planned=p, due=due)
        rec.submitted = time.monotonic()
        try:
            rec.rid = self.eng.submit(p.prompt, p.max_new)
            self.by_rid[rec.rid] = rec
        except Exception as e:  # noqa: BLE001 — a refusal is a failure
            rec.rejected = f"{type(e).__name__}: {e}"
        self.run.recs.append(rec)
        self.run.lateness.append(rec.submitted - due)

    def observe(self, done, st: Step) -> list:
        """Stamp the tokens that appeared during the step; note the decode
        rows and one-shot prefills it ran; return closed-loop finishes."""
        finished = []
        seen = [r for r in self.eng.pool.owner if r is not None] + list(done)
        for req in seen:
            rec = self.by_rid.get(req.request_id)
            if rec is None:
                continue
            rec.req = req
            old, new = len(rec.times), len(req.tokens)
            if new > old:
                rec.times.extend([st.t1] * (new - old))
                if old == 0 and rec.prompt_len <= self.eng.prefill_chunk:
                    st.oneshot.append(rec.prompt_len)
                if new - old - (old == 0) > 0:   # one decode token this step
                    st.decode.append(rec.prompt_len + new - 2)
        for req in done:
            rec = self.by_rid.get(req.request_id)
            if rec is not None:
                finished.append(rec)
        return finished

    def loop(self) -> None:
        run, plan = self.run, self.plan
        t_load = time.monotonic()
        closed = plan.kind == "closed"
        if closed:
            run.open_loop = False
            queue = []
            nxt = [iter(c) for c in plan.clients]
            for it in nxt:
                self.submit(next(it), t_load)
            firsts = list(run.recs)
        else:
            queue = sorted(plan.requests, key=lambda r: r.due_s)
            run.open_loop = plan.kind == "poisson"
            run.t_open = t_load + plan.lead_in_s
            run.t_close = run.t_open + run.seconds
        qi = 0
        win_span = None
        while True:
            now = time.monotonic()
            if closed and not run.t_open and all(r.times for r in firsts):
                run.t_open = now + plan.lead_in_s
                run.t_close = run.t_open + run.seconds
            if run.t_open and now >= run.t_close:
                break
            # the trace covers the window's last seconds, so that stopping
            # it, which blocks the host for seconds, falls after the close
            if self.trace_s and run.t_open and not self.tracing and \
                    now >= max(run.t_open, run.t_close - self.trace_s):
                jax.profiler.start_trace(self.trace_dir)
                win_span = jax.profiler.TraceAnnotation("bench.window")
                win_span.__enter__()
                self.tracing = True
                run.trace_span = (time.monotonic(), None)
            with span("submit", self.tracing):
                while qi < len(queue) and t_load + queue[qi].due_s <= now:
                    self.submit(queue[qi], t_load + queue[qi].due_s)
                    qi += 1
            if self.eng.has_work():
                st = Step(t0=time.monotonic(), t1=0.0)
                with span("step", self.tracing):
                    done = self.eng.step()
                st.t1 = time.monotonic()
                with span("observe", self.tracing):
                    ends = self.observe(done, st)
                run.steps.append(st)
                if closed:
                    for rec in ends:
                        p = next(nxt[rec.planned.client], None)
                        if p is not None:
                            self.submit(p, st.t1)
            else:
                wake = run.t_close if run.t_open else now + 0.01
                if self.trace_s and run.t_open and not self.tracing:
                    wake = min(wake, run.t_close - self.trace_s)
                if qi < len(queue):
                    wake = min(wake, t_load + queue[qi].due_s)
                with span("wait", self.tracing):
                    time.sleep(max(0.0, wake - time.monotonic()))
        if self.tracing:
            win_span.__exit__(None, None, None)
            run.trace_span = (run.trace_span[0], time.monotonic())
            jax.profiler.stop_trace()
        self._chunk_steps()

    def _chunk_steps(self) -> None:
        """A prompt longer than the chunk prefills one chunk per tick, on
        consecutive ticks that end with the tick that emitted its first
        token; place each chunk on its tick."""
        chunk = self.eng.prefill_chunk
        by_end = {}
        for i, s in enumerate(self.run.steps):
            by_end.setdefault(s.t1, i)
        for rec in self.run.recs:
            p = rec.prompt_len
            if p <= chunk or not rec.times:
                continue
            last = by_end[rec.times[0]]
            n = -(-p // chunk)
            for c in range(n):
                i = last - (n - 1) + c
                if i >= 0:
                    self.run.steps[i].chunks.append(
                        (c * chunk, min(chunk, p - c * chunk), c == n - 1))

