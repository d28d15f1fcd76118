"""Executables built inside the window, from JAX's own compile events:
the growth of `jit.compiles` (by the compiler) plus `jit.cache-hits`
(loaded from the persistent cache).  A retrace for a new shape counts
here whatever the program names its span.  None where the program keeps
no `jit.*` counters."""

NAMES = ("jit.compiles", "jit.cache-hits")


def read(w):
    if w.counters is None:
        return None
    from jepsen_tpu import telemetry

    # The growth omits counters that did not move; the registry shows
    # whether the program counts executables at all (set-up built some).
    seen = telemetry.summary()["counters"]
    if not any(k in seen for k in NAMES):
        return None
    return sum(w.counters.get(k, 0) for k in NAMES)
