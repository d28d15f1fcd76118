"""Program spans and counters read over the window, per check."""

from __future__ import annotations


def named(w, *names) -> list:
    """The window's spans with one of `names`, as (t0, t1) in s."""
    return [(s["t0_unix_s"], s["t0_unix_s"] + s["dur_s"])
            for s in w.spans or () if s["name"] in names]


def outermost(intervals: list) -> list:
    """Drops every interval that lies inside another."""
    out = []
    for a, b in sorted(intervals, key=lambda iv: (iv[0], -iv[1])):
        if out and b <= out[-1][1]:
            continue
        out.append((a, b))
    return out


def total(intervals: list) -> float:
    return sum(b - a for a, b in intervals)


def span_per_check(w, *names):
    """Total time in the outermost spans of `names`, per check; None
    where the window has no such span."""
    iv = named(w, *names)
    if not iv:
        return None
    return total(outermost(iv)) / len(w.checks)
