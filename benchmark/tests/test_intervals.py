"""The readers of the pass spans (`harness/intervals.py`), on
synthetic windows."""

from types import SimpleNamespace as NS

import pytest

from benchmark.harness import intervals as iv
from benchmark.harness import spans
from benchmark.harness.spec import load_module
from benchmark.harness.window import Check, Window


def span(name, t0, t1, thread="main"):
    return {"name": name, "t0_unix_s": t0, "dur_s": t1 - t0,
            "thread": thread}


def window(span_list, n_checks=2, counters=None):
    checks = [Check(float(i), float(i + 1), 10, 0) for i in range(n_checks)]
    return Window(0.0, float(n_checks), checks, setup_s=0.0, spans=span_list,
                  counters={} if counters is None else counters)


def read(metric, w):
    return load_module("layers", metric).read(w)


def test_intervals():
    w = window([span("a", 3, 4), span("a", 0, 2), span("b", 1, 2.5, "t1"),
                span("c", 5, 6)])
    assert iv.union(w, "a", "b") == [[0, 2.5], [3, 4]]
    assert spans.total(iv.union(w, "a", "b")) == pytest.approx(3.5)
    assert iv.subtract([[0, 10]], [[1, 2], [1.5, 3], [9, 12]]) == \
        [[0, 1], [3, 9]]
    assert iv.subtract([[0, 1]], []) == [[0, 1]]


# One check (the second is idle, so per check halves each sum): split,
# then per-key packs on two threads that overlap, the stream with two
# chunk calls, the screen pass, the BFS, and the settle pass with a
# single-key pack inside it.
CHECK = [
    span("lifecycle.analyze", 0.00, 1.00),
    span("checker.IndependentChecker", 0.00, 1.00),
    span("ingest.split", 0.02, 0.10),
    span("ingest.pack", 0.10, 0.20),
    span("wgl.plan.pass.stream-witness", 0.25, 0.60),
    span("wgl.stream", 0.26, 0.58),
    span("wgl.stream.concat", 0.26, 0.30),
    span("wgl.witness.chunk", 0.30, 0.40),
    span("wgl.witness.compile", 0.45, 0.50),
    span("wgl.plan.pass.refute-screen", 0.60, 0.70),
    span("wgl.plan.pass.batched-bfs", 0.70, 0.75),
    span("wgl.plan.pass.settle-exact", 0.75, 0.95),
    span("checker.Linearizable", 0.76, 0.90, thread="t1"),
    span("ingest.pack", 0.77, 0.85, thread="t1"),
    span("checker.Linearizable", 0.80, 0.94, thread="t2"),
    span("ingest.pack", 0.81, 0.89, thread="t2"),
]


def test_pass_readers_on_a_synthetic_window():
    w = window(CHECK)
    # Split and pack; the settle pass's packs, which overlap on two
    # threads, count under settle_exact_s alone.
    assert read("encode_s", w) == pytest.approx((0.08 + 0.10) / 2)
    assert read("screen_s", w) == pytest.approx(0.10 / 2)
    assert read("batched_s", w) == pytest.approx(0.05 / 2)
    assert read("settle_exact_s", w) == pytest.approx(0.20 / 2)
    # The stream less its two chunk calls: 0.32 - 0.10 - 0.05.
    assert read("search_host_s", w) == pytest.approx(0.17 / 2)
    # By hand: the analyze span is [0, 1]; less the stream, [0.26,
    # 0.58]; less split [0.02, 0.10], pack [0.10, 0.20], screen [0.60,
    # 0.70], BFS [0.70, 0.75], settle [0.75, 0.95] (its packs inside).
    # Left: [0, 0.02], [0.20, 0.26], [0.58, 0.60], [0.95, 1.00].
    assert read("host_unattributed_s", w) == \
        pytest.approx((0.02 + 0.06 + 0.02 + 0.05) / 2)
    # host_outside_search_s counts 0.68 a check; the pass spans name
    # all but 0.15 of it.
    assert read("host_outside_search_s", w) == pytest.approx(0.68 / 2)


def test_the_witness_path_has_a_single_history_screen():
    w = window([span("lifecycle.analyze", 0.0, 1.0),
                span("ingest.pack", 0.0, 0.3),
                span("wgl.plan.pass.device-ladder", 0.3, 1.0),
                span("wgl.screen", 0.3, 0.35),
                span("wgl.witness", 0.4, 1.0),
                span("wgl.witness.chunk", 0.5, 0.9)], n_checks=1)
    assert read("encode_s", w) == pytest.approx(0.3)
    assert read("screen_s", w) == pytest.approx(0.05)
    assert read("search_host_s", w) == pytest.approx(0.2)
    assert read("host_unattributed_s", w) == pytest.approx(0.05)
    assert read("batched_s", w) is None
    assert read("settle_exact_s", w) is None


def test_a_program_without_the_pass_spans_reads_nothing():
    """The parent of these spans: the readers return None, and raise
    nothing, so its traced run leaves the metrics out."""
    w = window([span("lifecycle.analyze", 0.0, 1.0),
                span("wgl.witness", 0.4, 1.0)])
    for m in ("encode_s", "screen_s", "batched_s", "settle_exact_s",
              "host_unattributed_s"):
        assert read(m, w) is None, m
    assert read("host_unattributed_s", window(None)) is None


def test_jit_compiles_in_window(monkeypatch):
    from jepsen_tpu import telemetry

    registry = {}
    monkeypatch.setattr(telemetry, "summary",
                        lambda: {"counters": dict(registry)})
    # A program that keeps no jit.* counters reads nothing.
    assert read("jit_compiles_in_window", window([])) is None
    registry.update({"jit.compiles": 40, "jit.cache-hits": 12})
    # Set-up built executables; the window built none.
    assert read("jit_compiles_in_window", window([])) == 0
    assert read("jit_compiles_in_window", window(
        [], counters={"jit.compiles": 1, "jit.cache-hits": 2})) == 3
    assert read("jit_compiles_in_window", NS(counters=None)) is None
