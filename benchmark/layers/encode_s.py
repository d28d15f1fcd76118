"""Host encode: the subhistory split and packing (`ingest.split`,
`ingest.pack` spans), their union per check, less the packs inside the
settle pass, which `settle_exact_s` counts."""

from benchmark.harness import intervals, spans


def read(w):
    iv = intervals.union(w, "ingest.split", "ingest.pack")
    if not iv:
        return None
    settle = intervals.union(w, "wgl.plan.pass.settle-exact")
    return spans.total(intervals.subtract(iv, settle)) / len(w.checks)
