"""Paged-attention decode kernel against its roofline.

Work per tick and layer, for each decode row at position t: 4 * Hq * hd *
(t + 1) operations (Q.K and P.V over the live keys); bytes: its live pages,
t // page + 1 of them, each page * Hkv * hd keys and values in bf16, plus
the query (bf16) and output (f32). Dead pages and masked key-value heads
count for nothing. The least time per tick is the larger of operations /
peak and bytes / bandwidth; the share is the least time of the traced
ticks over the kernel's device time inside the `_decode_step` programs, in
%.

Over several chips the least time is the work over all their peaks
(`run.chips` x one chip's), and the kernel's time is the first chip's
(every chip runs the same program): a kernel that every chip runs whole,
on replicated operands, reads at most 1 / chips of what it would on one
chip. That is the waste such a cell shows."""
PATTERN = r"decode_kernel|paged_attn_decode"
PROGRAM = "_decode_step"


def least_s(sz, ps, positions, peaks):
    hq, hkv, hd = sz["heads"], sz["kv_heads"], sz["head_dim"]
    flops = sum(4 * hq * hd * (t + 1) for t in positions)
    byts = sum((t // ps + 1) * 2 * ps * hkv * hd * 2 + hq * hd * 6
               for t in positions)
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
    ps = run.engine["page_size"]
    per_tick = sum(least_s(run.sizes, ps, s.decode, run.peaks)
                   for s in ticks) / len(ticks)
    return 100.0 * per_tick / run.chips * n / secs
