"""Operations of every history decided in the window, over the window's
seconds (host clock)."""

from benchmark.harness.window import ops_per_s


def read(w):
    return ops_per_s(w)
