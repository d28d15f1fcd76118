"""The chunked witness (`ops/wgl_witness.py`): `wgl.witness` spans, per
check."""

from benchmark.harness import spans


def read(w):
    return spans.span_per_check(w, "wgl.witness")
