"""The stream witness (`ops/wgl_stream.py`): `wgl.stream` spans, per
check."""

from benchmark.harness import spans


def read(w):
    return spans.span_per_check(w, "wgl.stream")
