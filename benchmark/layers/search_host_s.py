"""Host time inside the search: the outermost `wgl.witness` and
`wgl.stream` spans less the synced chunk calls inside them
(`wgl.witness.chunk`, `wgl.witness.compile`), per check."""

from benchmark.harness import intervals, spans


def read(w):
    search = intervals.union(w, "wgl.witness", "wgl.stream")
    if not search:
        return None
    chunks = intervals.union(w, "wgl.witness.chunk", "wgl.witness.compile")
    return spans.total(intervals.subtract(search, chunks)) \
        / len(w.checks)
