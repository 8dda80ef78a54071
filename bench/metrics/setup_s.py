"""Set-up: process start to the window's opening. Loading, weight
creation, compilation or loading from the compile cache, warm-up, and the
load offered before the window (the traffic's lead-in)."""


def read(run):
    return run.t_open - run.t_proc
