"""One run of one cell: pool, warm-up, window, comparison, metrics.

`run` takes the system under test from the cell's family, or the one it
is handed (the control, a planted fault), so that the tests drive every
step but the look for a chip.
"""

from __future__ import annotations

import contextlib
import json
import sys
import time

from benchmark.harness import spec, trace as trace_mod
from benchmark.harness.window import Window, run_closed_loop

#: Counters that must not move in a clean window: plan-executor
#: fallbacks, every step of the degradation ladder, settle-memo hits.
FALLBACK = ("wgl.plan.fallback", "wgl.degrade.")
MEMO_HIT = "wgl.settle.memo-hit"


def _telemetry():
    from jepsen_tpu import telemetry

    return telemetry


def _counter_growth(before: dict, after: dict) -> dict:
    out = {}
    for k, v in after.items():
        if isinstance(v, (int, float)):
            d = v - before.get(k, 0)
            if d:
                out[k] = d
    return out


def summarize(system, family, result) -> dict:
    """What the comparison needs of one check's result, so that the
    result itself can be freed before the reference runs."""
    engines = family.engines(result)
    return {
        "verdicts": system.verdicts(result),
        "valid": result.get("valid") if isinstance(result, dict) else None,
        "engines": engines,
        "degraded": sum(e.endswith(("-degraded", "-nobackend"))
                        for e in engines),
    }


def limits(w: Window, truth: dict, traced: bool) -> dict:
    """Every number that decides `correct`, beside its limit.  `truth`
    maps a pool index to the reference's verdicts, one per key; each
    check's result is already `summarize`d."""
    wrong = degraded = 0
    for c in w.checks:
        if c.error is not None:
            continue
        got, want = c.result["verdicts"], truth[c.entry]
        wrong += sum(g != t for g, t in zip(got, want))
        wrong += abs(len(got) - len(want))
        if len(want) > 1:
            # The history's verdict is the conjunction of its keys'.
            wrong += c.result["valid"] != all(t is True for t in want)
        degraded += c.result["degraded"]
    undecided = sum(v not in (True, False)
                    for e in {c.entry for c in w.checks} for v in truth[e])
    out = {
        "verdicts_wrong": (wrong, 0),
        "checks_raised": (sum(c.error is not None for c in w.checks), 0),
        "reference_undecided": (undecided, 0),
        "degraded_engines": (degraded, 0),
    }
    if traced:
        grew = w.counters or {}
        out["fallback_counts"] = (
            sum(v for k, v in grew.items() if k.startswith(FALLBACK)), 0)
        out["memo_hits"] = (grew.get(MEMO_HIT, 0), 0)
    return out


def routing(w: Window) -> dict:
    """Which engine decided how many verdicts, per check, and in a
    traced window the settle counters' growth."""
    out: dict = {}
    for c in w.checks:
        for e in (c.result or {}).get("engines", ()):
            out[e] = out.get(e, 0) + 1
    out = {e: n / len(w.checks) for e, n in sorted(out.items())}
    if w.counters is not None:
        out.update({k: v for k, v in sorted(w.counters.items())
                    if k.startswith("wgl.settle.")})
    return out


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool,
        t_start: float, system=None, marks: dict = None) -> dict:
    """Runs the cell once; returns the result line's fields plus the
    comparisons under "checks".  `marks` holds the set-up steps run.py
    timed; this adds the pool and the warm-up, and the steps go to
    stderr with the routing, on lines before the comparisons."""
    fam, config = cell.family, cell.config
    marks = dict(marks or {})
    t = time.monotonic()
    pool = fam.generate(config, cell.traffic, seed)
    for e in pool:
        e.program_input = fam.to_program(config, e)
    marks["pool_s"] = time.monotonic() - t
    tel = _telemetry()
    tel.enable(traced)
    if system is None:
        system = fam.System(config)
    # Warm-up: every pool history once, so every shape the window uses
    # is compiled (or loaded from the cache) here.
    t = time.monotonic()
    for e in pool:
        system.check(e)
    marks["warm_up_s"] = time.monotonic() - t

    before = dict(tel.summary()["counters"]) if traced else None
    mark = tel.event_mark() if traced else None
    tracer = trace_mod.Tracer() if traced else None
    around = (lambda e: tracer.annotate("bench.check")) if traced else None
    with tracer.window() if traced else contextlib.nullcontext():
        t0, t1, checks = run_closed_loop(
            lambda e: system.check(pool[e]), len(pool),
            lambda e: pool[e].n_ops, seconds, around)
    w = Window(t0, t1, checks, setup_s=t0 - t_start)
    if traced:
        w.counters = _counter_growth(before,
                                     dict(tel.summary()["counters"]))
        w.spans = tel.events_between(mark, limit=10 ** 9)
        w.trace = tracer.reduce()
    device = device_record(w)
    # The program is done: keep what the comparison needs, free the
    # rest, then run the reference.
    for c in checks:
        if c.error is None:
            c.result = summarize(system, fam, c.result)
    t = time.monotonic()
    truth = {e: fam.reference_verdicts(pool[e])
             for e in sorted({c.entry for c in checks})}
    marks["reference_s"] = time.monotonic() - t
    checks_ = limits(w, truth, traced)
    print("setup " + json.dumps(marks), file=sys.stderr)
    print("routing " + json.dumps(routing(w)), file=sys.stderr)
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        v = spec.load_module("layers" if traced else "e2e",
                             m["name"]).read(w)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out = {
        "correct": all(v <= lim for v, lim in checks_.values()),
        "attempted": len(checks),
        "failed": sum(c.error is not None for c in checks),
        "metrics": metrics,
        "device": device,
    }
    if traced and w.trace is not None:
        out["breakdown"] = w.trace.breakdown(w.spans)
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks_.items()}
    return out


def device_record(w: Window) -> dict:
    import jax

    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    rec = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": max(peaks)}
    if w.trace is not None:
        rec["busy_s"] = w.trace.busy_s
        rec["window_s"] = w.trace.window_s
    return rec
