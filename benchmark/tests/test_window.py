"""The window arithmetic: a rate is all the work over all the window's
time, a percentile is over every check."""

import statistics
import time

from benchmark.harness import window as wm


def _window(durations, ops=100, gap=0.0):
    checks, t = [], 10.0
    for i, d in enumerate(durations):
        checks.append(wm.Check(t, t + d, ops, i % 3))
        t += d + gap
    return wm.Window(checks[0].t0, checks[-1].t1, checks, setup_s=1.0)


def test_rate_is_all_ops_over_the_whole_window():
    w = _window([0.5, 1.0, 1.5], ops=300, gap=0.25)
    assert w.seconds == 3.5
    assert wm.ops_per_s(w) == 900 / 3.5


def test_failed_checks_add_no_ops_but_keep_their_time():
    w = _window([1.0, 1.0], ops=10)
    w.checks[1].error = "boom"
    assert wm.ops_per_s(w) == 10 / 2.0


def test_p90_over_every_check():
    ds = [float(i) for i in range(1, 11)]
    w = _window(ds)
    want = statistics.quantiles(ds, n=100, method="inclusive")[89]
    assert wm.percentile([c.seconds for c in w.checks], 90) == want
    assert 9.0 < want < 10.0
    assert wm.percentile([3.0], 90) == 3.0


def test_closed_loop_keeps_every_check_whole():
    seen = []

    def check(e):
        seen.append(e)
        time.sleep(0.01)
        return e

    t0, t1, checks = wm.run_closed_loop(check, 3, lambda e: 7, 0.05)
    assert [c.entry for c in checks] == [i % 3 for i in range(len(checks))]
    assert t1 == checks[-1].t1 and t1 - t0 >= 0.05
    # The last check is the first to end past the deadline.
    assert all(c.t1 - t0 < 0.05 for c in checks[:-1])
    assert seen == [c.entry for c in checks]


def test_a_raise_is_a_failed_check_not_a_crash():
    def check(e):
        raise RuntimeError("device lost")

    _, _, checks = wm.run_closed_loop(check, 2, lambda e: 1, 0.0)
    assert checks[0].error == "RuntimeError: device lost"
