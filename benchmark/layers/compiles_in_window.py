"""Compiles inside the window: program spans named `*.compile` that
started and ended in it.  Zero when set-up warmed every shape."""


def read(w):
    return sum(s["name"].endswith(".compile") for s in w.spans or ())
