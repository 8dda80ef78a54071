"""From the profiler's trace to numbers: the device's busy time, time per
operation and per compiled program, and what the host was doing while the
device sat idle.

A `Trace` keeps three lists of (name, start_ns, duration_ns) events on one
clock: `ops` (the operations of the first TPU, line "XLA Ops" of plane
`/device:TPU:0`, each by its own name), `modules` (its compiled programs,
line "XLA Modules") and `host` (the harness's own spans), and `chips`, the
"XLA Ops" of every TPU plane in device order (the first is `ops`). A cell
runs one program on each of its chips, the same SPMD program over a mesh,
so the readers read the first TPU's plane and it stands for each chip:
`chip_busy_s` gives each chip's busy time, to see whether it does.
`from_xplane` reads a profiler output file;
`from_dict` reads the same lists as JSON, which is how a small recorded
trace is kept for the tests.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

HOST_SPANS = ("bench.window", "step", "submit", "observe", "wait")
WINDOW = "bench.window"


@dataclass
class Trace:
    ops: list
    modules: list
    host: list
    # every TPU plane's ops in device order, the first being `ops`
    # (from_xplane; empty in a recorded trace, which keeps `ops` alone)
    chips: list = field(default_factory=list)

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
        modules, host = [], []
        names = sorted((p.name for p in pd.planes
                        if re.fullmatch(r"/device:TPU:\d+", p.name)),
                       key=lambda n: int(n.rsplit(":", 1)[1]))
        chips = {n: [] for n in names}
        for plane in pd.planes:
            if plane.name in chips:
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        chips[plane.name].extend(
                            (op_name(e.name), int(e.start_ns),
                             int(e.duration_ns)) for e in line.events)
                    elif line.name == "XLA Modules" and \
                            plane.name == names[0]:
                        modules.extend((e.name, int(e.start_ns),
                                        int(e.duration_ns))
                                       for e in line.events)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                                for e in line.events if e.name in HOST_SPANS)
        ops = [sorted(chips[n]) for n in names]
        return cls(ops[0] if ops else [], sorted(modules), sorted(host), ops)

    def name_kernels(self, kernels: dict) -> None:
        """Rename the operations `kernels` maps ({operation: kernel}, from
        `kernel_names`) after their kernel, keeping the numeric suffix."""
        def named(name):
            _, dot, n = name.rpartition(".")
            k = kernels.get(name)
            return name if k is None else (k + dot + n if dot else k)
        for ops in self.chips or [self.ops]:   # in place: `ops` is chips[0]
            ops[:] = [(named(n), s, d) for n, s, d in ops]

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

    def busy_intervals(self, ops: list | None = None) -> list:
        """The union of the device's operation intervals in the window (of
        `ops`, another chip's, where given)."""
        lo, hi = self.window()
        out = []
        for _, a, b in sorted(self._clip(self.ops if ops is None else ops,
                                         lo, hi), key=lambda e: e[1]):
            if out and a <= out[-1][1]:
                out[-1][1] = max(out[-1][1], b)
            else:
                out.append([a, b])
        return out

    def busy_s(self) -> float:
        return sum(b - a for a, b in self.busy_intervals()) / 1e9

    def chip_busy_s(self) -> list:
        """Each chip's busy seconds in the window, in device order."""
        return [sum(b - a for a, b in self.busy_intervals(ops)) / 1e9
                for ops in (self.chips or [self.ops])]

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


_KERNEL = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*custom_call_target='
                     r'"tpu_custom_call".*op_name="[^"]*jit\((\w+)\)'
                     r'(?:/shard_map)?/pallas_call"', re.M)


def kernel_names(hlo_text: str) -> dict:
    """{operation: kernel} for the Mosaic kernels of a compiled program's
    HLO text, the kernel read from the `jit(<kernel>)` its `op_name` metadata
    ends in. On one chip a kernel's operation bears the kernel's name; under
    a mesh it runs in a shard_map and its operation is named after that
    (`shard_map.443`), and a trace's op events carry only names."""
    return {m.group(1): m.group(2) for m in _KERNEL.finditer(hlo_text)}


def op_name(event_name: str) -> str:
    """`%fusion.233 = f32[16,2048]{..} fusion(..)` -> `fusion.233`: the
    operation's own name, without the operands whose names it mentions."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def module_name(event_name: str) -> str:
    """`jit__decode_step(123)` -> `_decode_step`: the jitted function."""
    name = re.sub(r"\(.*\)$", "", event_name).strip()
    return name[4:] if name.startswith("jit_") else name
