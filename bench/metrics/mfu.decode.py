"""The whole step's share of the chips' bf16 peak: the model's useful
operations in the ticks of the traced window (bench/modelflops.py) over
the window's length times the peak of all the cell's chips (`run.chips` x
one chip's), in %. Work that every chip repeats counts once."""
from bench.modelflops import step_flops


def read(run):
    steps = run.traced_steps()
    if run.trace is None or not steps:
        return None
    a, b = run.trace_span
    flops = sum(step_flops(run.sizes, s) for s in steps)
    return 100.0 * flops / ((b - a) * run.peaks["flops_bf16"]
                            * run.chips) or None
