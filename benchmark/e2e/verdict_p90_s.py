"""The 90th percentile of the time to verdict over every check in the
window, each timed from the call into the system to its return (host
clock)."""

from benchmark.harness.window import percentile


def read(w):
    return percentile([c.seconds for c in w.checks], 90)
