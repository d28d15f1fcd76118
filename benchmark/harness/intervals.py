"""Unions of program spans over a window, for the per-layer readers of
the host passes: spans from threads that overlap are counted once."""

from __future__ import annotations

from benchmark.harness import spans, trace


def union(w, *names) -> list:
    """The union of the window's spans named `names`, as sorted
    disjoint [t0, t1] in s."""
    return trace.merge([list(iv) for iv in spans.named(w, *names)])


def subtract(xs: list, ys: list) -> list:
    """`xs` less `ys`, both sorted and disjoint."""
    out = []
    for a, b in xs:
        cur = a
        for c, d in ys:
            if d <= cur or c >= b:
                continue
            if c > cur:
                out.append([cur, c])
            cur = max(cur, d)
        if cur < b:
            out.append([cur, b])
    return out


def union_per_check(w, *names):
    """Seconds in the union of spans `names`, per check; None where the
    window has none of them."""
    iv = union(w, *names)
    return spans.total(iv) / len(w.checks) if iv else None
