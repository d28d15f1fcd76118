"""Host time no pass span names: what `host_outside_search_s` counts
(`lifecycle.analyze` less the search spans) less the union of the spans
that `encode_s`, `screen_s`, `batched_s` and `settle_exact_s` read, per
check.  None where the program has none of those spans."""

from benchmark.harness import intervals, spans

PASSES = ("ingest.split", "ingest.pack", "wgl.screen",
          "wgl.plan.pass.refute-screen", "wgl.plan.pass.batched-bfs",
          "wgl.plan.pass.settle-exact")


def read(w):
    analyze = intervals.union(w, "lifecycle.analyze")
    passes = intervals.union(w, *PASSES)
    if not analyze or not passes:
        return None
    search = intervals.union(w, "wgl.witness", "wgl.stream")
    outside = intervals.subtract(analyze, search)
    return spans.total(intervals.subtract(outside, passes)) \
        / len(w.checks)
