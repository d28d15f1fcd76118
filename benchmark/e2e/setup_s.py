"""Process start to window start: imports, device init, the pool,
warm-up and, in a run that compiles, compilation (host clock)."""


def read(w):
    return w.setup_s
