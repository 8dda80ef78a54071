"""Grouped-GEMM kernels of the decode tick against their roofline.

Work per tick and layer, for T decode rows and top-k routing over E
experts of width f on a model of width d: 2 * T * k * 3 * d * f operations
(gate, up and down projections of every routed pair); bytes: the weights
of the experts touched, E * (1 - (1 - k/E)^T) of them under uniform
routing, plus the rows read and written (bf16). Shared experts run outside
these kernels and count for nothing here. The least time per tick is the
larger of operations / peak and bytes / bandwidth; the share is the least
time of the traced ticks over the kernels' device time inside the
`_decode_step` programs, in %.

Over several chips the least time is the work over all their peaks
(`run.chips` x one chip's), and the kernels' time is the first chip's
(every chip runs the same program): a kernel that every chip runs whole,
on replicated operands, reads at most 1 / chips of what it would on one
chip. That is the waste such a cell shows."""
PATTERN = r"gmm"
PROGRAM = "_decode_step"


def least_s(sz, rows, peaks):
    d, f, E, k = sz["d_model"], sz["d_expert"], sz["experts"], sz["top_k"]
    flops = 2 * rows * k * 3 * d * f
    touched = E * (1.0 - (1.0 - k / E) ** rows)
    byts = touched * 3 * d * f * 2 + 2 * rows * d * 2
    return sz["layers"] * max(flops / peaks["flops_bf16"],
                              byts / peaks["hbm_bytes_per_s"])


def read(run):
    if run.trace is None:
        return None
    ticks = [s for s in run.traced_steps() if s.decode]
    n = len(run.trace.module_events(PROGRAM))
    secs = run.trace.op_seconds(PATTERN, PROGRAM)
    if not ticks or not n or secs <= 0:
        return None
    per_tick = sum(least_s(run.sizes, len(s.decode), run.peaks)
                   for s in ticks) / len(ticks)
    return 100.0 * per_tick / run.chips * n / secs
