"""The trace reduction: on a fake profile with known intervals, and on a
small trace recorded on the chip (one check of `indep200.mixed`), cut to
what the reduction reads: the host event `bench.window` and the "XLA
Ops" line of /device:TPU:0 with its ops' names (event stats and the
other planes and lines dropped; 5.2 MB -> 0.66 MB, the same reduction)."""

import os
from types import SimpleNamespace as NS

import pytest

from benchmark.harness import trace as tm
from benchmark.harness.spec import BENCH_DIR, load_module

FIXTURE = os.path.join(BENCH_DIR, "fixtures", "indep200-one-check.xplane.pb")
KERNEL = 'x custom-call(), custom_call_target="tpu_custom_call"'


def ev(name, a, b):
    return NS(name=name, start_ns=a, end_ns=b)


def profile(device_lines, window=(100, 1100)):
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev(tm.WINDOW, *window), ev("bench.check", 100, 600)])])
    planes = [host]
    for i, events in enumerate(device_lines):
        planes.append(NS(name=f"/device:TPU:{i}", lines=[
            NS(name="XLA Modules", events=[ev("jit_x", 0, 5000)]),
            NS(name=tm.OPS_LINE, events=events)]))
    planes.append(NS(name="/device:TPU:0 SparseCore", lines=[
        NS(name=tm.OPS_LINE, events=[ev("other", 100, 1100)])]))
    return NS(planes=planes)


def test_busy_is_the_union_inside_the_window():
    p = profile([[ev("%while.1 = s32[] while()", 0, 300),     # clipped at 100
                  ev(KERNEL, 150, 250),                        # nested
                  ev("%fusion.2 = s32[] fusion()", 500, 700),
                  ev("%fusion.3 = s32[] fusion()", 650, 800),  # overlaps
                  ev("%copy.4 = s32[] copy()", 1050, 2000)]])  # clipped
    r = tm.reduce_profile(p)
    assert r.window_s == 1000 / 1e9
    assert r.busy_s == (200 + 300 + 50) / 1e9
    assert r.kernel_s(load_module("layers", "search_kernel_s").NAME) == \
        100 / 1e9
    assert r.kernel_s("no such kernel") is None
    assert sorted(r.gaps) == [(0.2e-6, 0.2e-6), (0.7e-6, 0.25e-6)]
    assert r.breakdown([])["device_ops"][0] == ["%while.1 while", 200 / 1e9]


def test_busy_is_averaged_over_the_chips_that_ran():
    r = tm.reduce_profile(profile([[ev("a = s32[] add()", 100, 600)],
                                   [ev("a = s32[] add()", 100, 300)],
                                   []]))
    assert r.chips == 2 and r.busy_s == (500 + 200) / 2 / 1e9


def test_gaps_are_named_by_the_innermost_program_span():
    r = tm.reduce_profile(profile([[ev("a = s32[] add()", 100, 200),
                                    ev("a = s32[] add()", 900, 1100)]]),
                          t0_wall=50.0)
    spans = [{"name": "lifecycle.analyze", "t0_unix_s": 50.0,
              "dur_s": 1e-6},
             {"name": "wgl.stream", "t0_unix_s": 50.0 + 0.3e-6,
              "dur_s": 0.5e-6}]
    assert r.breakdown(spans)["idle_gaps"] == [["wgl.stream", 0.7e-6]]


def test_a_trace_without_the_window_is_refused():
    with pytest.raises(ValueError):
        tm.reduce_profile(NS(planes=[]))


def test_short_names():
    assert tm.short_name("%while.125 = (s32[]{:T(128)}, u32[4]) while("
                         "(s32[]) %tuple.260), condition=%c") == \
        "%while.125 while"
    assert tm.short_name('%body.5 = (s32[1,8]{1,0}) custom-call(s32[1] %b),'
                         ' custom_call_target="tpu_custom_call"') == \
        "%body.5 custom-call tpu_custom_call"


def _union(intervals):
    """A second, plain computation of the union: a sweep over sorted
    endpoints."""
    total, depth, last = 0, 0, None
    for t, d in sorted([(a, 1) for a, b in intervals] +
                       [(b, -1) for a, b in intervals],
                       key=lambda x: (x[0], -x[1])):
        if depth > 0:
            total += t - last
        depth += d
        last = t
    return total


def test_recorded_chip_trace():
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(FIXTURE)
    r = tm.reduce_profile(pd)
    w0 = w1 = None
    ops = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == tm.WINDOW:
                    w0, w1 = e.start_ns, e.end_ns
                if plane.name == "/device:TPU:0" and line.name == "XLA Ops":
                    ops.append((e.name, e.start_ns, e.end_ns))
    inside = [(max(a, w0), min(b, w1)) for _, a, b in ops
              if b > w0 and a < w1]
    kernel = sum(min(b, w1) - max(a, w0) for n, a, b in ops
                 if 'custom_call_target="tpu_custom_call"' in n
                 and b > w0 and a < w1)
    assert r.chips == 1
    assert r.window_s == pytest.approx((w1 - w0) / 1e9, rel=1e-12)
    assert r.busy_s == pytest.approx(_union(inside) / 1e9, rel=1e-9)
    assert 0 < r.busy_s < r.window_s
    got = r.kernel_s(load_module("layers", "search_kernel_s").NAME)
    assert got == pytest.approx(kernel / 1e9, rel=1e-9) and got > 0
    # The numbers as first read from this trace.
    assert (r.window_s, r.busy_s, got) == pytest.approx(RECORDED, rel=1e-9)


# Read on the chip run that recorded it (my chip run, PR 22): idle share
# 90.33%, one check of 0.71 s.
RECORDED = (0.711955148, 0.068819818, 0.006833452999999999)
