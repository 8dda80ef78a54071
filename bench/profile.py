"""From the profiler's trace to numbers: the device's busy time, time per
operation and per compiled program, and what the host was doing while the
device sat idle.

A `Trace` keeps three lists of (name, start_ns, duration_ns) events on one
clock: `ops` (the operations of the first TPU, line "XLA Ops" of plane
`/device:TPU:0`, each by its own name), `modules` (its compiled programs,
line "XLA Modules") and `host` (the harness's own spans). Every cell runs
on one chip, so the first TPU is the chip used. `from_xplane` reads a profiler output file;
`from_dict` reads the same lists as JSON, which is how a small recorded
trace is kept for the tests.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass

HOST_SPANS = ("bench.window", "step", "submit", "observe", "wait")
WINDOW = "bench.window"


@dataclass
class Trace:
    ops: list
    modules: list
    host: list

    @classmethod
    def from_dict(cls, d: dict) -> "Trace":
        return cls(*(sorted(tuple(e) for e in d[k])
                     for k in ("ops", "modules", "host")))

    @classmethod
    def from_xplane(cls, log_dir: str) -> "Trace":
        from jax.profiler import ProfileData
        paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace under {log_dir}, found "
                               f"{len(paths)}")
        pd = ProfileData.from_file(paths[0])
        ops, modules, host = [], [], []
        chips = sorted((p.name for p in pd.planes
                        if re.fullmatch(r"/device:TPU:\d+", p.name)),
                       key=lambda n: int(n.rsplit(":", 1)[1]))
        for plane in pd.planes:
            if chips and plane.name == chips[0]:
                for line in plane.lines:
                    dst = {"XLA Ops": ops, "XLA Modules": modules}.get(
                        line.name)
                    if dst is ops:
                        ops.extend((op_name(e.name), int(e.start_ns),
                                    int(e.duration_ns)) for e in line.events)
                    elif dst is not None:
                        dst.extend((e.name, int(e.start_ns),
                                    int(e.duration_ns)) for e in line.events)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                                for e in line.events if e.name in HOST_SPANS)
        return cls(sorted(ops), sorted(modules), sorted(host))

    # ------------------------------------------------------------ window

    def window(self) -> tuple[int, int]:
        """The traced window: the harness's `bench.window` span."""
        w = [e for e in self.host if e[0] == WINDOW]
        if not w:
            raise ValueError("trace holds no bench.window span")
        _, s, d = max(w, key=lambda e: e[2])
        return s, s + d

    def _clip(self, events, lo: int, hi: int):
        for name, s, d in events:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                yield name, a, b

    def busy_intervals(self) -> list:
        """The union of the device's operation intervals in the window."""
        lo, hi = self.window()
        out = []
        for _, a, b in sorted(self._clip(self.ops, lo, hi),
                              key=lambda e: e[1]):
            if out and a <= out[-1][1]:
                out[-1][1] = max(out[-1][1], b)
            else:
                out.append([a, b])
        return out

    def busy_s(self) -> float:
        return sum(b - a for a, b in self.busy_intervals()) / 1e9

    def window_s(self) -> float:
        lo, hi = self.window()
        return (hi - lo) / 1e9

    # ------------------------------------------------------ by program

    def module_events(self, program: str) -> list:
        """Events of the compiled program `program` (the jitted function's
        name, as in `jit_<name>`) that lie inside the window."""
        lo, hi = self.window()
        return [e for e in self.modules
                if module_name(e[0]) == program and lo <= e[1]
                and e[1] + e[2] <= hi]

    def op_seconds(self, pattern: str, program: str | None = None) -> float:
        """Device seconds of the operations whose name matches `pattern`
        (a regular expression, searched), inside the window and, with
        `program`, inside that program's runs."""
        rx = re.compile(pattern)
        spans = sorted((s, s + d) for _, s, d in self.module_events(program)
                       ) if program else [self.window()]
        starts = [a for a, _ in spans]
        total = 0
        for name, s, d in self.ops:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s + d <= spans[i][1] and rx.search(name):
                total += d
        return total / 1e9

    # ------------------------------------------------------- breakdown

    def top_ops(self, n: int = 10) -> list:
        """The device operations that took most time, by name with its
        numeric suffix removed, each counting its own time only: an
        operation that holds others (a `while` over the layers) is charged
        what its inner operations leave."""
        lo, hi = self.window()
        tot: dict[str, int] = {}
        stack: list = []            # [key, end, own time] of open holders

        def close(upto: int) -> None:
            while stack and stack[-1][1] <= upto:
                key, _, own = stack.pop()
                tot[key] = tot.get(key, 0) + own
        for name, a, b in sorted(self._clip(self.ops, lo, hi),
                                 key=lambda e: (e[1], -e[2])):
            close(a)
            if stack:
                stack[-1][2] -= min(b, stack[-1][1]) - a
            stack.append([re.sub(r"\.\d+$", "", name), b, b - a])
        close(hi + 1)
        return [[k, v / 1e9] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list:
        """Device idle time in the window, by the harness span that covers
        most of each gap ("other" where none does), largest first."""
        lo, hi = self.window()
        busy = self.busy_intervals()
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        spans = [e for e in self.host if e[0] != WINDOW]
        tot: dict[str, int] = {}
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            cover = {}
            for name, s, d in spans:
                o = min(b, s + d) - max(a, s)
                if o > 0:
                    cover[name] = cover.get(name, 0) + o
            key = max(cover, key=cover.get) if cover else "other"
            tot[key] = tot.get(key, 0) + (b - a)
        return [[k, v / 1e9] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def op_name(event_name: str) -> str:
    """`%fusion.233 = f32[16,2048]{..} fusion(..)` -> `fusion.233`: the
    operation's own name, without the operands whose names it mentions."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def module_name(event_name: str) -> str:
    """`jit__decode_step(123)` -> `_decode_step`: the jitted function."""
    name = re.sub(r"\(.*\)$", "", event_name).strip()
    return name[4:] if name.startswith("jit_") else name
