"""The witness's wide rung (`ops/wgl.py` `check_wgl_device`, info
window `WIDE_INFO_WINDOW`), run when the narrow rung found no witness
and its plan dropped info columns: `wgl.witness.wide` spans, per
check."""

from benchmark.harness import spans


def read(w):
    return spans.span_per_check(w, "wgl.witness.wide")
