"""The witness's narrow rung (`ops/wgl.py` `check_wgl_device`, info
window `NARROW_INFO_WINDOW`): `wgl.witness.narrow` spans, per check."""

from benchmark.harness import spans


def read(w):
    return spans.span_per_check(w, "wgl.witness.narrow")
