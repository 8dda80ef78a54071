"""The program's own spans and scopes in a profiler trace, and the readers
built on them.

`ProgramTrace` is a `profile.Trace` (its lists and readers unchanged) that
also keeps:

  spans   (name, start_ns, duration_ns, args) of the host events the
          program names `engine.*` (serving/engine.py),
          `args` the counters the span carries (`engine.step` carries the
          tick's `TickRecord.counters()`);
  scopes  {operation name: scope path} for the operations of the decode
          program, the `op_name` metadata the model's `jax.named_scope`s
          leave (`jit(_decode_step)/while/body/moe/experts/...`), read from
          the compiled program's HLO text (`hlo_scopes`): a TPU v5e's op
          events carry only their names and times, and the names are the
          HLO instructions'.

Readers (each returns None where the trace holds nothing to read, as a
trace of a program without these spans and scopes does):

  tick_host_ms        median over traced ticks of `engine.step` minus the
                      `*.wait` spans inside it: the host's own time a tick
  attn_dead_steps     1 - sum(live_pages) / sum(grid_pages) over traced
                      decode ticks, in %: the paged-attention grid steps
                      that walk retired rows or pages past a row's position
  decode_scope_ms     device ms per `_decode_step` run by model scope
                      (`kv`, `attn`, `moe`, `embed`, `head`, `unscoped`),
                      each operation charged its own time (as `top_ops`)
  idle_phases         device idle time charged to the innermost host span
                      covering it: engine phases inside the harness's spans
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import statistics
from dataclasses import dataclass, field

from bench.profile import WINDOW, Trace

PREFIX = "engine."
PROGRAM = "_decode_step"
# the model's scopes (models/model.py, models/blocks.py): each operation
# of a decode tick lies under at most one of them
KV = ("kv_read", "kv_write")
MODEL_SCOPES = KV + ("attn", "moe", "embed", "head")
MOE_SCOPES = ("router", "dispatch", "experts", "shared", "combine")


@dataclass
class ProgramTrace(Trace):
    spans: list = field(default_factory=list)
    scopes: dict = field(default_factory=dict)

    @classmethod
    def from_dict(cls, d: dict) -> "ProgramTrace":
        base = Trace.from_dict(d)
        spans = sorted(((n, int(s), int(du), dict(a))
                        for n, s, du, a in d.get("spans", [])),
                       key=lambda e: (e[1], -e[2], e[0]))
        return cls(base.ops, base.modules, base.host, spans=spans,
                   scopes=dict(d.get("scopes", {})))

    @classmethod
    def from_xplane(cls, log_dir: str, hlo_text: str) -> "ProgramTrace":
        """The trace under `log_dir`; `hlo_text` is the compiled decode
        program's HLO, which names the scopes of its operations."""
        from jax.profiler import ProfileData
        base = Trace.from_xplane(log_dir)
        path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        spans = sorted(((e.name, int(e.start_ns), int(e.duration_ns),
                         dict(e.stats))
                        for plane in ProfileData.from_file(path).planes
                        if plane.name.startswith("/host:")
                        for line in plane.lines for e in line.events
                        if e.name.startswith(PREFIX)),
                       key=lambda e: (e[1], -e[2], e[0]))
        tr = cls(base.ops, base.modules, base.host, base.chips, spans)
        hlo = hlo_scopes(hlo_text)
        tr.scopes = {n: hlo[n] for n, *_ in tr.program_ops() if n in hlo}
        return tr

    # ------------------------------------------------------------ spans

    def window_spans(self, name: str) -> list:
        lo, hi = self.window()
        return [e for e in self.spans
                if e[0] == name and lo <= e[1] and e[1] + e[2] <= hi]

    # -------------------------------------------------------- by program

    def program_ops(self, program: str = PROGRAM) -> list:
        """(name, start, end, own ns) of the operations inside the window's
        runs of `program`; an operation that holds others (the layer
        `while`) owns what its inner operations leave."""
        runs = sorted((s, s + d) for _, s, d in self.module_events(program))
        starts = [a for a, _ in runs]
        out, stack = [], []
        for name, s, d in sorted(self.ops, key=lambda e: (e[1], -e[2])):
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s + d > runs[i][1]:
                continue
            while stack and stack[-1][2] <= s:
                out.append(tuple(stack.pop()))
            if stack:
                stack[-1][3] -= min(s + d, stack[-1][2]) - s
            stack.append([name, s, s + d, d])
        out.extend(tuple(e) for e in reversed(stack))
        return out


_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$')
_META = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
_CALLS = re.compile(r'calls=%?([\w.\-]+)')
_COMP = re.compile(r'^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$')


def hlo_scopes(text: str) -> dict:
    """{instruction name: op_name metadata} of an HLO module's text, for
    every instruction: its own metadata, else (a fusion, a loop) that of
    the computation it calls, whose root's metadata stands for it, or the
    last metadata inside it where the root has none (a tuple); "" for an
    instruction the compiler made from no operation of the program."""
    own, calls, last = {}, {}, {}
    comp = None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        meta = _META.search(rest)
        root = line.lstrip().startswith("ROOT")
        if meta:
            own[name] = meta.group(1)
            if comp is not None and (root or comp not in last
                                     or not last[comp][1]):
                last[comp] = (meta.group(1), root)
        else:
            own[name] = ""
            c = _CALLS.search(rest)
            if c:
                calls[name] = c.group(1)
    for name, comp in calls.items():
        if not own[name] and comp in last:
            own[name] = last[comp][0]
    return own


def scope_of(path: str | None) -> str:
    """The model scope an operation's op_name path lies under ("kv" for
    either KV scope; "moe/<part>" for a part of the MoE layer), "unscoped"
    for none or an empty path, "unmapped" where nothing named the
    operation (None)."""
    if path is None:
        return "unmapped"
    parts = path.split("/")[:-1] if path else []
    for s in MODEL_SCOPES:
        if s in parts:
            if s == "moe":
                sub = [p for p in MOE_SCOPES if p in parts]
                return "moe/" + sub[0] if sub else "moe"
            return "kv" if s in KV else s
    return "unscoped"


# ----------------------------------------------------------- readers

def tick_host_ms(tr: ProgramTrace) -> float | None:
    """Median over the window's ticks of `engine.step` less the `*.wait`
    spans inside it, in ms."""
    steps = tr.window_spans("engine.step")
    if not steps:
        return None
    waits = sorted((s, s + d) for n, s, d, _ in tr.spans
                   if n.startswith(PREFIX) and n.endswith(".wait"))
    starts = [a for a, _ in waits]
    host = []
    for _, s, d, _ in steps:
        i = bisect.bisect_left(starts, s)
        blocked = 0
        while i < len(waits) and waits[i][0] < s + d:
            a, b = waits[i]
            blocked += min(b, s + d) - a
            i += 1
        host.append(d - blocked)
    return statistics.median(host) / 1e6


def attn_dead_steps(tr: ProgramTrace) -> float | None:
    """Share of the decode kernel's grid steps over the window's decode
    ticks that staged no live page, in %."""
    ticks = [a for _, _, _, a in tr.window_spans("engine.step")
             if a.get("grid_pages", 0) > 0]
    grid = sum(a["grid_pages"] for a in ticks)
    if not grid:
        return None
    return 100.0 * (1.0 - sum(a["live_pages"] for a in ticks) / grid)


def decode_scope_ms(tr: ProgramTrace) -> dict | None:
    """Device ms per decode-program run, by `scope_of` each operation, with
    `kv`, `attn`, `moe` (its parts summed), `embed`, `head`, `unscoped`
    and, where the trace names no path for an operation, `unmapped`."""
    runs = len(tr.module_events(PROGRAM))
    ops = tr.program_ops()
    if not runs or not ops or not tr.scopes:
        return None
    tot: dict[str, float] = {}
    for name, _, _, own in ops:
        key = scope_of(tr.scopes.get(name))
        tot[key] = tot.get(key, 0.0) + own
        if key.startswith("moe/"):
            tot["moe"] = tot.get("moe", 0.0) + own
    return {k: v / runs / 1e6 for k, v in sorted(tot.items())}


def idle_phases(tr: ProgramTrace, n: int = 10) -> list:
    """Device idle time in the window, charged piece by piece to the
    innermost host span covering it (the shortest of the spans that cover
    the piece: an engine phase inside `engine.step` inside the harness's
    `step`), "other" where none does; largest first, in s."""
    lo, hi = tr.window()
    busy = tr.busy_intervals()
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    spans = sorted([(s, s + d, name) for name, s, d in tr.host
                    if name != WINDOW] +
                   [(s, s + d, name) for name, s, d, _ in tr.spans])
    starts = [s for s, _, _ in spans]
    longest = max((b - a for a, b, _ in spans), default=0)
    tot: dict[str, int] = {}
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        j0 = bisect.bisect_left(starts, a - longest)
        j1 = bisect.bisect_left(starts, b)
        cover = [sp for sp in spans[j0:j1] if sp[1] > a]
        cuts = sorted({a, b} | {x for sp in cover for x in sp[:2]
                                if a < x < b})
        for u, v in zip(cuts, cuts[1:]):
            inner = [sp for sp in cover if sp[0] <= u and v <= sp[1]]
            key = min(inner, key=lambda sp: sp[1] - sp[0])[2] if inner \
                else "other"
            tot[key] = tot.get(key, 0) + (v - u)
    return [[k, v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
