"""Latent-attention decode kernel (`_mla_paged_decode`) against its
roofline: the least time of the traced decode ticks' rows
(bench/mla_counts.py `decode_kernel_least_s`) over the kernel's device time
inside the `_decode_step` programs, in %. Nothing to read where the decode
tick runs no such kernel."""
from bench.mla_counts import decode_kernel_least_s

PATTERN = r"_mla_paged_decode"
PROGRAM = "_decode_step"


def read(run):
    if run.trace is None or "kv_lora" not in run.sizes:
        return None
    ticks = [s for s in run.traced_steps() if s.decode]
    n = len(run.trace.module_events(PROGRAM))
    secs = run.trace.op_seconds(PATTERN, PROGRAM)
    if not ticks or not n or secs <= 0:
        return None
    ps = run.engine["page_size"]
    per_tick = sum(decode_kernel_least_s(run.sizes, ps, s.decode, run.peaks)
                   for s in ticks) / len(ticks)
    return 100.0 * per_tick / run.chips * n / secs
