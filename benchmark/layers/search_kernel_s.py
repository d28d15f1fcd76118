"""The Pallas search kernels (the witness's barrier sweep from
`_make_pallas_sweep`, which the stream witness runs too): the device
time of their events in the profiler trace, found by name, per check.
A Pallas kernel reaches the trace as an XLA custom call to Mosaic; its
op name is its HLO text, which names that target."""

#: How the kernels' device events are named in the trace.
NAME = r'custom_call_target="tpu_custom_call"'


def read(w):
    if w.trace is None:
        return None
    s = w.trace.kernel_s(NAME)
    return None if s is None else s / len(w.checks)
