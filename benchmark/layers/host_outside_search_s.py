"""Host encode, plan and screens: the `lifecycle.analyze` span less the
search spans (`wgl.witness`, `wgl.stream`) inside it, per check."""

from benchmark.harness import spans


def read(w):
    analyze = spans.named(w, "lifecycle.analyze")
    if not analyze:
        return None
    search = spans.outermost(spans.named(w, "wgl.witness", "wgl.stream"))
    return (spans.total(analyze) - spans.total(search)) / len(w.checks)
