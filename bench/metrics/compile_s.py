"""Seconds of backend compilation before the window opened, from
jax.monitoring: compiles, and loads of programs from the persistent compile
cache, which JAX times alike."""


def read(run):
    return sum(e[2] for e in run.compiles.between(0, run.t_open)
               if e[1] == "compile")
