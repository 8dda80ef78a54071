"""Device time per decode-tick program (`_decode_step`), the mean over its
runs in the traced window."""


def read(run):
    ev = run.trace.module_events("_decode_step") if run.trace else []
    return sum(d for _, _, d in ev) / len(ev) / 1e6 if ev else None
