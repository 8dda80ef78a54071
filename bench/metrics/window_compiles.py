"""Programs compiled, or loaded from the compile cache, while the window
was open: 0 when warm-up covered every shape the traffic uses."""


def read(run):
    return float(sum(e[1] == "compile" for e in
                     run.compiles.between(run.t_open, run.t_close)))
