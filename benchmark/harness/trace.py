"""The profiler trace of a traced window, reduced to device busy time,
device op time by name, and idle gaps.

`Tracer` starts `jax.profiler` around the window (Python tracing off)
and marks the window and each check with host annotations.  `reduce`
reads the `.xplane.pb` it wrote: the window is the host event
`bench.window`; device ops are the events of the "XLA Ops" line of each
`/device:TPU:<n>` plane, named by their HLO text.  Busy time is the
union of those events' intervals inside the window, averaged over the
chips that ran any; an idle gap is the time between two merged busy
intervals.  XLA ops nest (a `while` holds its body's ops), so op times
by name overlap; the union does not.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import tempfile
import time
from dataclasses import dataclass

WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"/device:TPU:\d+")
OPS_LINE = "XLA Ops"


@dataclass
class Reduction:
    window_s: float
    busy_s: float
    op_s: dict                  # device op name -> seconds, all chips
    gaps: list                  # [(start_s, seconds)], start from t0
    t0_wall: float = 0.0        # host wall clock at the window's start
    chips: int = 0

    def kernel_s(self, pattern: str):
        """Seconds of device ops whose name matches `pattern`; None
        where none ran."""
        rx = re.compile(pattern)
        hits = [s for n, s in self.op_s.items() if rx.search(n)]
        return sum(hits) if hits else None

    def breakdown(self, spans) -> dict:
        """The ten device ops that took most time, and idle time by what
        the host was doing: the innermost program span around each
        gap's middle."""
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:10]
        ops = [(short_name(n), s) for n, s in ops]
        by = {}
        for start, s in self.gaps:
            name = host_activity(spans or (), self.t0_wall + start + s / 2)
            by[name] = by.get(name, 0.0) + s
        gaps = sorted(by.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [list(x) for x in ops],
                "idle_gaps": [list(x) for x in gaps]}


def short_name(hlo: str) -> str:
    """`%while.125 = (...) while(...)` -> `%while.125 while`; a custom
    call keeps its target."""
    head, _, rest = hlo.partition(" = ")
    kind = re.search(r"[\]})] ([a-z][\w-]*)\(", rest)
    out = head + (" " + kind.group(1) if kind else "")
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    return out + (" " + target.group(1) if target else "")


def host_activity(spans, t: float) -> str:
    best = None
    for s in spans:
        if s["t0_unix_s"] <= t <= s["t0_unix_s"] + s["dur_s"]:
            if best is None or s["t0_unix_s"] > best["t0_unix_s"]:
                best = s
    return best["name"] if best else "no program span"


def merge(intervals: list) -> list:
    """The union of [a, b] intervals, as sorted disjoint intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def reduce_profile(profile, t0_wall: float = 0.0) -> Reduction:
    """Reduces a `jax.profiler.ProfileData`."""
    w0 = w1 = None
    for plane in profile.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        w0, w1 = e.start_ns, e.end_ns
    if w0 is None:
        raise ValueError(f"no {WINDOW!r} event in the trace")
    op_s: dict = {}
    busy, chips, gaps = 0.0, 0, []
    for plane in profile.planes:
        if not DEVICE_PLANE.fullmatch(plane.name):
            continue
        inside = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                a, b = max(e.start_ns, w0), min(e.end_ns, w1)
                if b > a:
                    inside.append([a, b])
                    op_s[e.name] = op_s.get(e.name, 0.0) + (b - a) / 1e9
        if not inside:
            continue
        chips += 1
        merged = merge(inside)
        busy += sum(b - a for a, b in merged) / 1e9
        if chips == 1:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            gaps = [((edges[i] - w0) / 1e9, (edges[i + 1] - edges[i]) / 1e9)
                    for i in range(0, len(edges), 2)]
    return Reduction(window_s=(w1 - w0) / 1e9,
                     busy_s=busy / chips if chips else 0.0,
                     op_s=op_s, gaps=[g for g in gaps if g[1] > 0],
                     t0_wall=t0_wall, chips=chips)


def load(path: str, t0_wall: float = 0.0) -> Reduction:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), t0_wall)


class Tracer:
    """The profiler around one window, writing under TMPDIR."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.t0_wall = 0.0

    def annotate(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        try:
            self.t0_wall = time.time()
            with self.annotate(WINDOW):
                yield
        finally:
            jax.profiler.stop_trace()

    def reduce(self) -> Reduction:
        try:
            path = glob.glob(os.path.join(
                self.dir, "plugins", "profile", "*", "*.xplane.pb"))[0]
            return load(path, self.t0_wall)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
