"""Host spans on the profiler's clock.

`span(name, **counters)` marks a stretch of host work as a
`jax.profiler.TraceAnnotation`: while a profiler session runs, the span
lands in the same trace as the device's operations, on the same clock,
with `counters` as its arguments (`set_metadata(**more)` on the open span
adds arguments known only at its end). With no session running a span
costs well under a microsecond and records nothing; it never touches the
device.

Names are dotted by owner and phase (`engine.step`, `engine.decode.wait`);
a span whose name ends in `.wait` covers the host blocked on the device,
and sits inside the phase that waits.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation


def span(name: str, **counters) -> TraceAnnotation:
    """A context manager that records `name` over its body while a
    profiler session runs. Counter values are ints, floats or strings
    without commas (the trace encodes arguments as `k=v,k=v`)."""
    return TraceAnnotation(name, **counters)
