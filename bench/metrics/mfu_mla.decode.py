"""The whole step's share of the chips' bf16 peak for a latent-attention
configuration, defined like `mfu.decode`: the model's useful operations in
the ticks of the traced window, counted as the published (non-absorbed)
forward's with its dense layers (bench/mla_counts.py), over the window's
length times the peak of all the cell's chips, in %."""
from bench.mla_counts import step_flops


def read(run):
    steps = run.traced_steps()
    if run.trace is None or not steps or "kv_lora" not in run.sizes:
        return None
    a, b = run.trace_span
    flops = sum(step_flops(run.sizes, s) for s in steps)
    return 100.0 * flops / ((b - a) * run.peaks["flops_bf16"]
                            * run.chips) or None
