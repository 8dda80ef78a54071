"""Share of the decode tick's device time spent in collectives, in %: the
device time of the collective operations (all-gather, all-reduce,
reduce-scatter, collective-permute, all-to-all, their -start and -done
halves, and the TPU's `async-collective-start` and `-done`) inside the
`_decode_step` programs over the device time of those programs, on the
first chip (every chip runs the same program). A program over one chip
holds none, so only cells over several chips list it."""
PATTERN = (r"all-gather|all-reduce|reduce-scatter|collective-permute|"
           r"all-to-all|async-collective")
PROGRAM = "_decode_step"


def read(run):
    ev = run.trace.module_events(PROGRAM) if run.trace else []
    if not ev:
        return None
    total = sum(d for _, _, d in ev) / 1e9
    return 100.0 * run.trace.op_seconds(PATTERN, PROGRAM) / total
