"""setup_s: process start to the start of the window (corpus generation
or reuse, JAX start-up, plan, compile and the warm job), host clock."""


def read(rec):
    return rec.setup_s
