"""The device's idle share of the traced window: 1 - (union of device
op intervals / window), from the profiler trace, in %."""


def read(w):
    t = w.trace
    if t is None or not t.window_s or not t.busy_s:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
