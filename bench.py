#!/usr/bin/env python
"""Headline benchmark: TPU-offloaded linearizability checking throughput.

Generates the BASELINE.json north-star workload — a 100k-op concurrent
cas-register history with a high indeterminate-op ratio — and measures
how fast the device WGL search (ops/wgl.py: witness fast path + exact
frontier BFS) decides it.  The reference's checker (knossos's CPU WGL,
checker.clj:214-233) is the baseline: the driver-defined target is a
verdict in <60 s on this history (BASELINE.md), i.e. ~1,667 ops
checked/sec; knossos itself times out.

Prints exactly one JSON line:
  {"metric": ..., "value": N, "unit": "ops/s", "vs_baseline": N,
   "phases": {"generate": s, "pack": s, "warmup": s, "check": s}}
vs_baseline > 1.0 means faster than the 60-s north-star floor.
On any failure the line still prints, with value 0 and an "error" field.
"phases" is a coarse wall-clock breakdown and is always present on
success; with JEPSEN_TELEMETRY=1 the run additionally exports the full
span registry (telemetry.json + Perfetto trace.json) to
JEPSEN_TELEMETRY_DIR (default store/bench) without touching stdout.

Flags (env):
  JEPSEN_BENCH_OPS        history length        (default 100000)
  JEPSEN_BENCH_INFO       indeterminate-op rate (default 0.05)
  JEPSEN_BENCH_PROCS      worker concurrency    (default 16)
  JEPSEN_BENCH_TIME_LIMIT per-check budget, s   (default 300)
  JEPSEN_BENCH_PLATFORM   "cpu" runs on the CPU backend: the one
                          explicit way to rehearse.  Unset, a run that
                          finds no TPU exits non-zero.
  JEPSEN_BENCH_SCALE_OPS  second-metric scale-point size (default
                          20000000; "0" disables the scale point)
  JEPSEN_BENCH_MIXED_KEYS third-metric mixed-shape key count (default
                          200; "0" disables the mixed point)
  JEPSEN_BENCH_FLEET_TENANTS  fleet-point tenant ceiling (default 16;
                          "0" disables the fleet point)

Capture trustworthiness: every measurement line carries "loadavg"
(os.getloadavg at capture), "spread_ratio" (max/min over the measured
reps), and "capture_quality" ("ok", or "noisy"/"contended"/both when
the spread stayed >1.5x or the 1-minute load exceeded the core count).
When a capture looks noisy or contended, run_bench re-measures inside
the wall budget it already holds before settling on a median — the
trajectory reads the annotation instead of flagging phantom
regressions.

Third metric (this PR): "independent_mixed_throughput" — the
invalid-heavy jepsen.independent shape (200 keys x 100 ops, ~15% of
keys carrying a planted violation) through the cohort settling ladder
(parallel/independent.py), median of 3 memo-cold reps, embedded under
"mixed" in the same single JSON line.

Second headline metric (VERDICT r4 #4): BASELINE.md's other north
star is "max history length to verdict @ 300 s".  After the
throughput measurement, a second child process generates a
scale-point history with the VECTORIZED packed generator
(utils/histgen.py random_register_packed — the Op-level generator
costs 4x the checker's own decision time at 20M ops) and decides it
under the 300 s budget.  The result is embedded in the SAME single
JSON line under "scale" (keeping the one-line contract).  The point is
auto-sized down when the wall budget left can't fit the configured
size at the measured throughput, so the bench never blows the
driver's patience chasing the second metric.

One process per chip: the parent never imports JAX.  It runs each
measurement in a child process, one after another, so exactly one
process at a time holds the chip.  A child that finds no TPU (and no
JEPSEN_BENCH_PLATFORM=cpu) fails, and so does the run; a side point
that fails makes the run exit non-zero with the main line still
printed.
"""

import json
import os
import sys
import time

#: Workload-shape knobs, declared once: every child reads them here, so
#: a default changed in one place can't silently mix shapes.
WORKLOAD_KNOBS = (
    ("JEPSEN_BENCH_OPS", "100000"),
    ("JEPSEN_BENCH_INFO", "0.05"),
    ("JEPSEN_BENCH_PROCS", "16"),
)


def knob(name: str) -> str:
    default = dict(WORKLOAD_KNOBS)[name]
    return os.environ.get(name, default)


def emit(value: float, vs: float, **extra) -> None:
    rec = {
        "metric": "wgl_linearizability_throughput",
        "value": round(value, 1),
        "unit": "ops/s",
        "vs_baseline": round(vs, 3),
    }
    rec.update(extra)
    print(json.dumps(rec))


def init_backend() -> str:
    """Places the compile cache and initializes the JAX backend.  No
    fallback: JEPSEN_BENCH_PLATFORM=cpu is the one way onto the CPU,
    and otherwise a backend other than the TPU is an error."""
    import jax

    from jepsen_tpu import compile_cache

    compile_cache.place()
    if os.environ.get("JEPSEN_BENCH_PLATFORM", "") == "cpu":
        jax.config.update("jax_platforms", "cpu")
        jax.devices()
        return "cpu"
    from jepsen_tpu.ops import degrade

    degrade.note_backend()  # a chip held elsewhere fails here, clearly
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise RuntimeError(
            f"bench needs a TPU and found {platform!r} (set "
            "JEPSEN_BENCH_PLATFORM=cpu to rehearse on the CPU)"
        )
    return platform


def _loadavg() -> list:
    """[1, 5, 15]-minute load averages, or [] where unsupported —
    a missing loadavg must never cost a measurement."""
    try:
        return [round(x, 2) for x in os.getloadavg()]
    except (OSError, AttributeError):
        return []


def _contended() -> bool:
    """True when the 1-minute loadavg exceeds the core count: more
    runnable threads than cores means every timeslice is shared and
    wall-clock measurements are dilated."""
    la = _loadavg()
    return bool(la) and la[0] > (os.cpu_count() or 1)


def _capture_conditions(times: list) -> dict:
    """Trustworthiness annotation for a multi-rep capture: the machine
    load at capture time, the rep spread ratio, and a one-word quality
    verdict.  "ok" = tight spread on an uncontended machine — the
    number is the kernel's; "noisy" (spread > 1.5x survived the retry
    budget) or "contended" (loadavg above the core count) mark numbers
    that measured the machine's mood, so the perf trajectory can
    discount them instead of flagging a phantom regression."""
    out: dict = {"loadavg": _loadavg()}
    quality = []
    if len(times) >= 2 and min(times) > 0:
        ratio = max(times) / min(times)
        out["spread_ratio"] = round(ratio, 3)
        if ratio > 1.5:
            quality.append("noisy")
    if _contended():
        quality.append("contended")
    out["capture_quality"] = "+".join(quality) if quality else "ok"
    return out


def run_bench() -> int:
    n_ops = int(knob("JEPSEN_BENCH_OPS"))
    info_rate = float(knob("JEPSEN_BENCH_INFO"))
    procs = int(knob("JEPSEN_BENCH_PROCS"))
    budget = float(os.environ.get("JEPSEN_BENCH_TIME_LIMIT", "300"))
    baseline_floor = 100_000 / 60.0  # north-star: 100k ops decided in 60 s

    try:
        platform = init_backend()

        from jepsen_tpu import telemetry
        from jepsen_tpu.history.packed import pack_history
        from jepsen_tpu.models import cas_register
        from jepsen_tpu.ops.wgl import check_wgl_device
        from jepsen_tpu.utils.histgen import random_register_history

        telemetry.reset()
        # Coarse phase timers are ALWAYS on (one monotonic call per
        # phase — nowhere near the <2% contract) so the JSON line's
        # "phases" field never depends on JEPSEN_TELEMETRY; the spans
        # additionally feed the full trace when telemetry is enabled.
        phases: dict = {}
        model = cas_register()
        pm = model.packed()
        t_ph = time.monotonic()
        with telemetry.span("bench.generate"):
            h = random_register_history(
                n_ops, procs=procs, info_rate=info_rate, seed=45100
            )
        phases["generate"] = round(time.monotonic() - t_ph, 3)
        t_ph = time.monotonic()
        with telemetry.span("bench.pack"):
            packed = pack_history(h, pm.encode)
        phases["pack"] = round(time.monotonic() - t_ph, 3)

        # Warm-up on a short prefix so JIT compilation of the kernels is
        # excluded from the measured run (first TPU compile is tens of
        # seconds).  width_hint forces the warm-up onto the same window
        # bucket the real history will use, so its compile hits cache.
        # (transfer="device"'s span bucket S can still differ between
        # warm-up and real history — that one extra compile lands in
        # rep 1 and the median-of-3 below absorbs it.)
        from jepsen_tpu.ops.wgl_witness import plan_width

        width = plan_width(packed)
        warm = random_register_history(
            4096, procs=procs, info_rate=info_rate, seed=7
        )
        warm_start = time.monotonic()
        with telemetry.span("bench.warmup"):
            check_wgl_device(
                pack_history(warm, pm.encode), pm,
                time_limit_s=min(120.0, budget / 2),
                width_hint=width,
            )
        phases["warmup"] = round(time.monotonic() - warm_start, 3)
        # The measured run gets whatever budget the warm-up left, so
        # total wall time stays bounded by ~budget (the driver kills
        # overruns before the JSON line prints — round-1 rc=124).
        budget = max(30.0, budget - (time.monotonic() - warm_start))

        # Median of three measured reps, so one slow rep does not set
        # the recorded number.  Once ANY rep has a valid verdict, later reps
        # are refinement only; when the budget is exhausted we keep the
        # measurements already in hand rather than starting a rep that
        # would overshoot the stated budget.
        times = []
        for _ in range(3):
            t0 = time.monotonic()
            with telemetry.span("bench.check"):
                res = check_wgl_device(packed, pm, time_limit_s=budget)
            elapsed = time.monotonic() - t0
            if res.valid is not True:
                break
            times.append(elapsed)
            budget -= elapsed
            if budget <= 0:
                break
        # Load-aware retry: a wide rep spread (>1.5x) or a contended
        # machine (more runnable threads than cores) means the capture
        # measured the NEIGHBORS, not the kernel.  Extra reps run only
        # inside the wall budget already granted — the median tightens
        # when the noise was transient, and the capture-quality field
        # below tells the perf trajectory when it wasn't.
        extra = 0
        while (len(times) >= 2 and extra < 3
               and budget > max(times)
               and (max(times) / min(times) > 1.5 or _contended())):
            t0 = time.monotonic()
            with telemetry.span("bench.check"):
                res = check_wgl_device(packed, pm, time_limit_s=budget)
            elapsed = time.monotonic() - t0
            if res.valid is not True:
                break
            times.append(elapsed)
            budget -= elapsed
            extra += 1
        phases["check"] = round(sum(times), 3)
        if not times:
            emit(
                0.0,
                0.0,
                error=(
                    f"expected valid verdict, got {res.valid} "
                    f"({res.reason}) after {elapsed:.1f}s"
                ),
                platform=platform,
            )
            return 1
        times.sort()
        elapsed = times[len(times) // 2]

        ops_per_s = packed.n / elapsed
        if telemetry.enabled():
            # Full span/trace export for telemetry-enabled bench runs;
            # stdout stays untouched (one-JSON-line contract).
            telemetry.export(os.environ.get(
                "JEPSEN_TELEMETRY_DIR", os.path.join("store", "bench")
            ))
        # Degradation/retry/timeout counters ride next to the phase
        # wall-clocks: a run that only stays fast by falling down the
        # WGL ladder is a regression, and it must show in the same JSON
        # line the perf trajectory reads.  Requires JEPSEN_TELEMETRY=1
        # (counters are off otherwise); omitted when empty so the
        # steady-state line doesn't grow a noise field.
        resilience = telemetry.resilience_counters()
        emit(
            ops_per_s,
            ops_per_s / baseline_floor,
            platform=platform,
            elapsed_s=round(elapsed, 3),
            n_ops=packed.n,
            phases=phases,
            **({"resilience": resilience} if resilience else {}),
            # Multi-rep evidence (VERDICT r4 #8): the rep count and
            # min/max spread retire the single-rep ±30% caveat — a
            # last-good record with reps>=3 is a median, not a mood.
            reps=len(times),
            spread_s=[round(times[0], 3), round(times[-1], 3)],
            **_capture_conditions(times),
        )
        return 0
    except Exception as e:  # noqa: BLE001 — the JSON line must print
        import traceback

        traceback.print_exc(file=sys.stderr)
        emit(0.0, 0.0, error=f"{type(e).__name__}: {e}")
        return 1


def _roofline_probe(pm) -> "Optional[dict]":
    """Post-metric roofline probe: one small device check with
    telemetry + a throwaway profile store enabled, summarized per pass
    (telemetry/roofline.py).  Runs AFTER the timed reps so the scale
    metric's measurement conditions stay identical to every prior
    BENCH_r* trajectory; restores telemetry state on exit."""
    import tempfile

    from jepsen_tpu import telemetry
    from jepsen_tpu.ops.wgl import check_wgl_device
    from jepsen_tpu.telemetry import profile, roofline
    from jepsen_tpu.utils.histgen import random_register_packed

    prev_enabled = telemetry.enabled()
    prev_store = profile.store_path()
    tmp = tempfile.mkdtemp(prefix="bench-roofline-")
    telemetry.enable(True)
    profile.set_store(tmp)
    try:
        probe = random_register_packed(
            100_000, procs=int(knob("JEPSEN_BENCH_PROCS")),
            info_rate=float(knob("JEPSEN_BENCH_INFO")),
            seed=11, model=pm,
        )
        check_wgl_device(probe, pm, time_limit_s=60.0)
        recs = profile.read(os.path.join(tmp, profile.PROFILE_FILE))
        if not recs:
            return None
        return {
            "probe_ops": int(probe.n),
            "passes": roofline.summarize(recs),
        }
    finally:
        telemetry.enable(prev_enabled)
        profile.set_store(
            os.path.dirname(prev_store) if prev_store else None)


def _measure_ingest(pm) -> "Optional[dict]":
    """Measured ingest throughput: ops/s through the PackedBuilder
    append -> snapshot -> finish path (the streaming checker's ingest
    primitive), over a pre-built op list so op generation stays out of
    the measurement.  Measures both the scalar per-op path and the
    columnar append_many fast path (the batch size matches the remote
    feed's FLUSH_OPS frame) and reports the gain."""
    from jepsen_tpu.history.packed import PackedBuilder
    from jepsen_tpu.streaming.remote import FLUSH_OPS
    from jepsen_tpu.utils.histgen import random_register_history

    ops = list(random_register_history(
        200_000, procs=int(knob("JEPSEN_BENCH_PROCS")),
        info_rate=float(knob("JEPSEN_BENCH_INFO")), seed=13,
    ))

    def scalar() -> float:
        b = PackedBuilder(pm.encode)
        t0 = time.monotonic()
        for i, o in enumerate(ops):
            b.append(o)
            if (i + 1) % 50_000 == 0:
                b.snapshot()
        b.finish()
        return time.monotonic() - t0

    def batched() -> float:
        b = PackedBuilder(pm.encode)
        t0 = time.monotonic()
        for lo in range(0, len(ops), FLUSH_OPS):
            b.append_many(ops[lo:lo + FLUSH_OPS])
            if (lo // FLUSH_OPS) % (50_000 // FLUSH_OPS) == \
                    (50_000 // FLUSH_OPS) - 1:
                b.snapshot()
        b.finish()
        return time.monotonic() - t0

    t_scalar = min(scalar(), scalar())
    t_batch = min(batched(), batched())
    if t_scalar <= 0 or t_batch <= 0:
        return None
    return {
        "ops_per_s": round(len(ops) / t_batch),
        "scalar_ops_per_s": round(len(ops) / t_scalar),
        "batch_gain": round(t_scalar / t_batch, 3),
    }


def run_scale() -> int:
    """Scale-point child (JEPSEN_BENCH_SCALE_CHILD=1): one big
    history, one verdict, one JSON line."""
    budget = float(os.environ.get("JEPSEN_BENCH_SCALE_BUDGET", "300"))
    target = int(os.environ.get("JEPSEN_BENCH_SCALE_OPS", "20000000"))
    rate_hint = float(os.environ.get("JEPSEN_BENCH_RATE_HINT", "0"))
    wall = float(os.environ.get("JEPSEN_BENCH_SCALE_WALL", "300"))
    try:
        platform = init_backend()
        if rate_hint > 0:
            # Fit the point inside what's actually left: generation is
            # ~1 s / 10M rows, the check runs at ~rate_hint; leave 40%
            # slack for compile + a loaded machine.
            fit = int(rate_hint * max(30.0, wall - 60.0) * 0.6)
            # Shrink to what fits, but never below 1M (unless the
            # caller explicitly asked for less) and never above the
            # configured size.
            target = min(target, max(1_000_000, fit))

        from jepsen_tpu.models import cas_register
        from jepsen_tpu.ops.wgl import check_wgl_device
        from jepsen_tpu.ops.wgl_witness import plan_width
        from jepsen_tpu.utils.histgen import random_register_packed

        pm = cas_register().packed()
        packed = random_register_packed(
            target,
            procs=int(knob("JEPSEN_BENCH_PROCS")),
            info_rate=float(knob("JEPSEN_BENCH_INFO")),
            seed=45100, model=pm,
        )
        width = plan_width(packed)

        def checked(pack, limit):
            return check_wgl_device(pack, pm, time_limit_s=limit,
                                    width_hint=width)

        # Small same-width warm-up so compile stays out of the metric.
        warm = random_register_packed(
            50_000, procs=int(knob("JEPSEN_BENCH_PROCS")),
            info_rate=float(knob("JEPSEN_BENCH_INFO")),
            seed=7, model=pm,
        )
        checked(warm, 120.0)
        # JEPSEN_BENCH_SCALE_REPS>=3 records median+spread; the
        # embedded scale point keeps the single-rep default (its wall
        # slice is whatever the primary metric left over).
        reps = max(1, int(os.environ.get("JEPSEN_BENCH_SCALE_REPS",
                                         "1")))
        budget0 = budget
        times = []
        for _ in range(reps):
            t0 = time.monotonic()
            res = checked(packed, budget)
            dt = time.monotonic() - t0
            if res.valid is not True:
                break
            times.append(dt)
            budget -= dt
            if budget <= 0:
                break
        if times:
            times.sort()
            dt = times[len(times) // 2]
        rec = {
            "metric": "scale_ops_to_verdict",
            "ops": int(packed.n),
            "valid": res.valid,
            "elapsed_s": round(dt, 2),
            "budget_s": budget0,
            "platform": platform,
            **({"reps": len(times),
                "spread_s": [round(times[0], 3), round(times[-1], 3)]}
               if len(times) > 1 else {}),
            **_capture_conditions(times if times else [dt]),
        }
        from jepsen_tpu import telemetry

        resilience = telemetry.resilience_counters()
        if resilience:
            # Same contract as run_bench: a scale point reached only by
            # degrading down the WGL ladder is flagged in its own line.
            rec["resilience"] = resilience
        if res.valid is True:
            rate = packed.n / dt
            rec["ops_per_s"] = round(rate)
            # The north-star form: capacity at the 300 s budget,
            # extrapolated from the measured flat rate (design notes
            # measured the checker rate flat from 100k to 20M ops).
            rec["max_ops_at_300s"] = int(rate * 300.0)
        else:
            rec["error"] = f"verdict {res.valid} ({res.reason})"
        # Roofline + ingest observability fields (advisory: a probe
        # failure never costs the scale point its primary metric).
        try:
            rec["roofline"] = _roofline_probe(pm)
        except Exception:  # noqa: BLE001
            rec["roofline"] = None
        try:
            ing_rec = _measure_ingest(pm)
            ing = ing_rec["ops_per_s"] if ing_rec else None
            rec["ingest_ops_per_s"] = ing
            if ing_rec:
                rec["ingest_scalar_ops_per_s"] = ing_rec["scalar_ops_per_s"]
                rec["ingest_batch_gain"] = ing_rec["batch_gain"]
            if res.valid is True and ing:
                # The share of end-to-end verdict lag the ingest path
                # would claim at this point's scale (ROADMAP item 5's
                # "profile before attacking" number).
                ingest_s = packed.n / ing
                rec["ingest_share_of_verdict_lag"] = round(
                    ingest_s / (ingest_s + dt), 4)
        except Exception:  # noqa: BLE001
            rec["ingest_ops_per_s"] = None
        print(json.dumps(rec))
        return 0 if res.valid is True else 1
    except Exception as e:  # noqa: BLE001 — the JSON line must print
        import traceback

        traceback.print_exc(file=sys.stderr)
        print(json.dumps({
            "metric": "scale_ops_to_verdict", "ops": 0,
            "valid": None, "error": f"{type(e).__name__}: {e}",
        }))
        return 1


def run_scale_online() -> int:
    """Online scale-point child (JEPSEN_BENCH_SCALE_ONLINE_CHILD=1):
    the streaming counterpart of run_scale.  Instead of one post-hoc
    decision over a finished pack, the history is consumed as it
    "arrives" — the packed stream is replayed in stable-prefix slices
    through streaming.FrontierCarry, so the witness search overlaps the
    run — and the headline number is the VERDICT LAG: wall time from
    the last op landing to the verdict.  Emits one JSON line,

      {"metric": "scale_ops_to_verdict_online", "ops": N,
       "verdict_lag_s": s, "elapsed_s": s, "ops_per_s": r,
       "lag_fraction": lag/elapsed, ...}

    embedded under "scale_online" in the main line by the parent.  The
    acceptance shape (ISSUE 7) is lag_fraction < 0.10: online checking
    must deliver the verdict within 10% of the run length after the
    run ends.

    A slice boundary at row k with stable bound s = inv[k] is exactly a
    PackedBuilder snapshot: every prefix row has inv < s (rows are
    inv-sorted) and every later completion has ret > inv >= s, which is
    the precondition FrontierCarry.advance documents — so this replay
    exercises the identical consumption rule as a live run, minus the
    client threads."""
    budget = float(os.environ.get("JEPSEN_BENCH_SCALE_BUDGET", "300"))
    target = int(os.environ.get("JEPSEN_BENCH_SCALE_ONLINE_OPS",
                                "2000000"))
    rate_hint = float(os.environ.get("JEPSEN_BENCH_RATE_HINT", "0"))
    wall = float(os.environ.get("JEPSEN_BENCH_SCALE_WALL", "300"))
    slices = max(4, int(os.environ.get("JEPSEN_BENCH_SCALE_ONLINE_SLICES",
                                       "24")))
    try:
        platform = init_backend()
        if rate_hint > 0:
            # Same fit rule as run_scale, with a harder haircut: each
            # advance replans the prefix (O(n log n) host numpy), so
            # the online loop carries ~slices/2 extra plan passes.
            fit = int(rate_hint * max(30.0, wall - 60.0) * 0.4)
            target = min(target, max(200_000, fit))

        import numpy as np

        from jepsen_tpu.history.packed import PackedOps
        from jepsen_tpu.models import cas_register
        from jepsen_tpu.streaming.frontier import FrontierCarry
        from jepsen_tpu.utils.histgen import random_register_packed

        pm = cas_register().packed()
        packed = random_register_packed(
            target,
            procs=int(knob("JEPSEN_BENCH_PROCS")),
            info_rate=float(knob("JEPSEN_BENCH_INFO")),
            seed=45100, model=pm,
        )
        n = packed.n
        zeros = np.zeros(0, dtype=packed.preds.dtype)

        def prefix(k: int) -> PackedOps:
            # Witness-only view of the first k rows; preds/horizon are
            # BFS-only columns the frontier never reads.
            z = np.zeros(k, dtype=packed.preds.dtype) if k else zeros
            return PackedOps(
                inv=packed.inv[:k], ret=packed.ret[:k],
                process=packed.process[:k], status=packed.status[:k],
                f=packed.f[:k], a0=packed.a0[:k], a1=packed.a1[:k],
                src_index=packed.src_index[:k], preds=z, horizon=z,
            )

        # Warm the chunk-fn compile outside the measured window with a
        # small same-model stream (width buckets may still differ on
        # the big stream; any residual compile lands in elapsed_s, not
        # in the lag tail, because it hits the first advance).
        warm = random_register_packed(
            50_000, procs=int(knob("JEPSEN_BENCH_PROCS")),
            info_rate=float(knob("JEPSEN_BENCH_INFO")),
            seed=7, model=pm,
        )
        fw = FrontierCarry(pm)
        fw.finalize(warm)

        fr = FrontierCarry(pm)
        t0 = time.monotonic()
        step = max(1, n // slices)
        for k in range(step, n, step):
            fr.advance(prefix(k), int(packed.inv[k]))
            if time.monotonic() - t0 > budget:
                break
        t_last = time.monotonic()  # the "run" ends: last op has landed
        valid = fr.finalize(packed)
        t_end = time.monotonic()
        lag = t_end - t_last
        total = t_end - t0
        rec = {
            "metric": "scale_ops_to_verdict_online",
            "ops": int(n),
            "valid": valid,
            "verdict_lag_s": round(lag, 3),
            "elapsed_s": round(total, 2),
            "ops_per_s": round(n / total) if total > 0 else 0,
            "lag_fraction": round(lag / total, 4) if total > 0 else None,
            "slices": slices,
            "budget_s": budget,
            "platform": platform,
            "frontier": {
                "blocks": fr.blocks_done,
                "bars": fr.bars_done,
                "chunks": fr.chunks,
                "device_s": round(fr.device_s, 2),
                **({"dead": fr.dead_reason} if fr.dead else {}),
            },
        }
        if valid is not True:
            rec["error"] = f"frontier could not prove: {fr.dead_reason}"
        print(json.dumps(rec))
        return 0 if valid is True else 1
    except Exception as e:  # noqa: BLE001 — the JSON line must print
        import traceback

        traceback.print_exc(file=sys.stderr)
        print(json.dumps({
            "metric": "scale_ops_to_verdict_online", "ops": 0,
            "valid": None, "error": f"{type(e).__name__}: {e}",
        }))
        return 1


def run_mixed() -> int:
    """Invalid-heavy independent-checking child
    (JEPSEN_BENCH_MIXED_CHILD=1): 200 keys x 100 ops with ~15% of keys
    carrying a planted violation, through IndependentChecker's
    settling ladder (stream witness -> memo -> refutation screens ->
    batched BFS -> parallel CPU settle).  The settle memo is cleared
    before every rep so the metric prices the cold ladder, not a memo
    replay.  One JSON line, embedded under "mixed" in the main line by
    the parent."""
    budget = float(os.environ.get("JEPSEN_BENCH_MIXED_BUDGET", "120"))
    n_keys = int(os.environ.get("JEPSEN_BENCH_MIXED_KEYS", "200"))
    key_ops = int(os.environ.get("JEPSEN_BENCH_MIXED_KEY_OPS", "100"))
    n_bad = max(1, round(n_keys * 0.15))
    try:
        platform = init_backend()

        from jepsen_tpu.checker.linearizable import Linearizable
        from jepsen_tpu.history.core import history as make_history
        from jepsen_tpu.models import cas_register
        from jepsen_tpu.parallel.independent import (
            IndependentChecker, clear_settle_memo, kv,
        )
        from jepsen_tpu.parallel.mesh import default_mesh
        from jepsen_tpu.utils.histgen import random_register_history

        ops = []
        for i in range(n_keys):
            h = random_register_history(
                key_ops, procs=4, info_rate=0.05, seed=i,
                bad=(i < n_bad),
            )
            ops += [o.replace(value=kv(f"k{i}", o.value)) for o in h]
        hist = make_history(ops)
        chk = IndependentChecker(
            Linearizable(cas_register(), time_limit_s=budget)
        )
        test = {"mesh": default_mesh()}

        times = []
        t_wall = time.monotonic()
        for rep in range(4):  # rep 0 = compile warm-up, never counted
            clear_settle_memo()
            t0 = time.monotonic()
            res = chk.check(test, hist, {})
            dt = time.monotonic() - t0
            ok = (res["valid"] is False
                  and res["failure-count"] == n_bad)
            if not ok:
                print(json.dumps({
                    "metric": "independent_mixed_throughput",
                    "error": (
                        f"expected invalid with {n_bad} failures, got "
                        f"valid={res['valid']} "
                        f"failures={res.get('failure-count')}"
                    ),
                    "platform": platform,
                }))
                return 1
            if rep > 0:
                times.append(dt)
            if time.monotonic() - t_wall > budget:
                break
        times.sort()
        rate = (len(hist) / 2) / times[len(times) // 2]
        rec = {
            "metric": "independent_mixed_throughput",
            "ops_per_s": round(rate, 1),
            "keys": n_keys,
            "key_ops": key_ops,
            "bad_keys": n_bad,
            "elapsed_s": round(times[len(times) // 2], 3),
            "reps": len(times),
            "spread_s": [round(times[0], 3), round(times[-1], 3)],
            "platform": platform,
            **_capture_conditions(times),
        }
        print(json.dumps(rec))
        return 0
    except Exception as e:  # noqa: BLE001 — the JSON line must print
        import traceback

        traceback.print_exc(file=sys.stderr)
        print(json.dumps({
            "metric": "independent_mixed_throughput",
            "error": f"{type(e).__name__}: {e}",
        }))
        return 1


def run_fleet_scale() -> int:
    """Fleet scale-point child (JEPSEN_BENCH_FLEET_CHILD=1): the
    multi-tenant axis (ISSUE 20) gets a trajectory like
    scale_ops_to_verdict has.  Ramps the number of concurrent monitor
    tenants — each a real `jepsen monitor` child process with its own
    rolling checker, series store, and pacing loop, exactly what a
    FleetSupervisor child is minus the suite daemons — doubling 1, 2,
    4, ... until a round breaks the verdict-lag SLO or the budget
    runs out.  A round of N tenants is SUSTAINED when every tenant's
    sampled `monitor.verdict-lag-s` series keeps its SLO burn under
    5%: at most 5% of samples above the lag threshold AND a p95 under
    it (one slow tick is absorbed; a shifted distribution is not).
    Emits one JSON line,

      {"metric": "fleet_tenants_sustained", "tenants": N,
       "p95_verdict_lag_s": worst sustained p95, "rounds": [...]}

    embedded under "fleet" in the main line by the parent."""
    budget = float(os.environ.get("JEPSEN_BENCH_FLEET_BUDGET", "150"))
    ceiling = int(os.environ.get("JEPSEN_BENCH_FLEET_TENANTS", "16"))
    rate = float(os.environ.get("JEPSEN_BENCH_FLEET_RATE", "500"))
    round_s = float(os.environ.get("JEPSEN_BENCH_FLEET_ROUND_S", "10"))
    lag_slo = float(os.environ.get("JEPSEN_BENCH_FLEET_LAG_SLO", "5.0"))
    burn_limit = 0.05
    import shutil
    import subprocess
    import tempfile

    from jepsen_tpu.telemetry.timeseries import read_disk_series

    def round_of(n: int, tmp: str) -> dict:
        dirs = [os.path.join(tmp, f"t{i}") for i in range(n)]
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        procs = [
            subprocess.Popen(
                [sys.executable, "-m", "jepsen_tpu.suites.kvdb",
                 "monitor", "--store-dir", d, "--rate", str(rate),
                 "--duration", str(round_s), "--keys", "2",
                 "--cadence", "0.5"],
                env=env, stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL,
            )
            for d in dirs
        ]
        # Import + run + drain; a wedged tenant is an SLO miss, not a
        # bench hang.
        deadline = time.monotonic() + round_s + 90.0
        rcs = []
        for pr in procs:
            try:
                rcs.append(pr.wait(
                    timeout=max(1.0, deadline - time.monotonic())))
            except subprocess.TimeoutExpired:
                pr.kill()
                pr.wait()
                rcs.append(-9)
        worst_p95, worst_burn, samples = 0.0, 0.0, 0
        for d in dirs:
            pts = [v for _, v in
                   read_disk_series(d, "monitor.verdict-lag-s")]
            if len(pts) < 3:
                return {"tenants": n, "sustained": False,
                        "reason": f"tenant produced {len(pts)} lag "
                                  f"samples (rcs={rcs})"}
            pts.sort()
            p95 = pts[int(0.95 * (len(pts) - 1))]
            burn = sum(1 for v in pts if v > lag_slo) / len(pts)
            worst_p95 = max(worst_p95, p95)
            worst_burn = max(worst_burn, burn)
            samples += len(pts)
        ok = worst_burn < burn_limit and worst_p95 <= lag_slo
        return {"tenants": n, "sustained": ok,
                "p95_verdict_lag_s": round(worst_p95, 3),
                "burn": round(worst_burn, 4), "samples": samples}

    t0 = time.monotonic()
    rounds, best = [], None
    try:
        n = 1
        while n <= ceiling:
            if time.monotonic() - t0 > budget:
                rounds.append({"tenants": n,
                               "skipped": "budget exhausted"})
                break
            tmp = tempfile.mkdtemp(prefix="bench-fleet-")
            try:
                r = round_of(n, tmp)
            finally:
                shutil.rmtree(tmp, ignore_errors=True)
            rounds.append(r)
            print(f"# fleet round: {r}", file=sys.stderr)
            if not r.get("sustained"):
                break
            best = r
            n *= 2
        rec = {
            "metric": "fleet_tenants_sustained",
            "tenants": best["tenants"] if best else 0,
            "p95_verdict_lag_s": (best or {}).get("p95_verdict_lag_s"),
            "lag_slo_s": lag_slo,
            "burn_limit": burn_limit,
            "rate_per_tenant": rate,
            "round_s": round_s,
            "rounds": rounds,
        }
        print(json.dumps(rec))
        return 0 if best else 1
    except Exception as e:  # noqa: BLE001 — the JSON line must print
        import traceback

        traceback.print_exc(file=sys.stderr)
        print(json.dumps({
            "metric": "fleet_tenants_sustained", "tenants": 0,
            "error": f"{type(e).__name__}: {e}", "rounds": rounds,
        }))
        return 1


def main() -> int:
    """Runs the bench in a child process under a hard wall-clock
    watchdog, then each side point in its own child, one after
    another: the parent never imports JAX, so one process at a time
    holds the chip.  A child past its deadline is killed and the run
    fails."""
    import subprocess

    if os.environ.get("JEPSEN_BENCH_SCALE_CHILD"):
        return run_scale()
    if os.environ.get("JEPSEN_BENCH_SCALE_ONLINE_CHILD"):
        return run_scale_online()
    if os.environ.get("JEPSEN_BENCH_MIXED_CHILD"):
        return run_mixed()
    if os.environ.get("JEPSEN_BENCH_FLEET_CHILD"):
        return run_fleet_scale()
    if os.environ.get("JEPSEN_BENCH_NO_WATCHDOG"):
        return run_bench()
    t_start = time.monotonic()
    # Total wall cap: the r02-r04 driver runs all finished inside the
    # budget+240 envelope without a kill, so the scale point must fit
    # under the same ceiling rather than raise it.
    wall_cap = 520.0
    budget = float(os.environ.get("JEPSEN_BENCH_TIME_LIMIT", "300"))
    deadline = budget + 240.0  # compile + generation slack
    env = dict(os.environ, JEPSEN_BENCH_NO_WATCHDOG="1")
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            timeout=deadline, env=env, capture_output=True,
        )
    except subprocess.TimeoutExpired as e:
        sys.stderr.write((e.stderr or b"").decode(errors="replace"))
        emit(0.0, 0.0, error=f"bench child ran past {deadline:.0f}s; "
                             "killed")
        return 1
    out = proc.stdout.decode(errors="replace")
    sys.stderr.write(proc.stderr.decode(errors="replace"))
    if proc.returncode != 0:
        sys.stdout.write(out)
        return proc.returncode
    failed = []
    for name, side in (("mixed", _with_mixed_point),
                       ("scale", _with_scale_point),
                       ("scale_online", _with_scale_online_point),
                       ("fleet", _with_fleet_point)):
        try:
            out = side(out, env, t_start, wall_cap)
        except Exception as e:  # noqa: BLE001 — keep the main line
            print(f"# {name} point failed: {e!r}", file=sys.stderr)
            failed.append(name)
            continue
        # Skipping for lack of wall budget is a choice; a child that
        # errored, crashed or ran past its deadline fails the run.
        _, rec = _last_json_line(out)
        sub = (rec or {}).get(name) or {}
        if "error" in sub or sub.get(
                "skipped", "wall budget exhausted") != "wall budget exhausted":
            failed.append(name)
    sys.stdout.write(out)
    if failed:
        print(f"# side points failed: {failed}", file=sys.stderr)
        return 1
    return 0


def _last_json_line(text: str):
    """(index, parsed) of the last valid JSON line in `text`, or
    (None, None) — the single line-detection rule shared by the
    scale-point merge and the killed-child forwarder."""
    lines = text.splitlines()
    found_i = found = None
    for i, ln in enumerate(lines):
        if ln.startswith("{"):
            try:
                found = json.loads(ln)
                found_i = i
            except ValueError:
                continue
    return found_i, found


def _with_mixed_point(out: str, env: dict, t_start: float,
                      wall_cap: float) -> str:
    """Runs the invalid-heavy mixed child inside what's left of the
    wall cap and embeds its record under "mixed" in the main JSON
    line.  Any failure leaves the main line untouched."""
    import subprocess

    if os.environ.get("JEPSEN_BENCH_MIXED_KEYS", "") == "0":
        return out
    lines = out.splitlines()
    main_i, main_rec = _last_json_line(out)
    if main_rec is None or main_rec.get("value", 0) <= 0:
        return out
    wall_left = wall_cap - (time.monotonic() - t_start)
    if wall_left < 80.0:
        main_rec["mixed"] = {"skipped": "wall budget exhausted"}
    else:
        env2 = dict(
            env,
            JEPSEN_BENCH_MIXED_CHILD="1",
            JEPSEN_BENCH_MIXED_BUDGET=str(
                min(120.0, max(30.0, wall_left - 40.0))
            ),
        )
        if main_rec.get("platform") != "tpu":
            # The mixed shape's parallelism lives in the mesh; a CPU
            # rehearsal gets the same 8-virtual-device split the test
            # suite measures (tests/test_whole_stack_perf.py), so the
            # recorded number is comparable to the committed floor.
            env2["XLA_FLAGS"] = (
                env2.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8"
            ).strip()
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__)],
                timeout=wall_left - 10.0, env=env2, capture_output=True,
            )
            sys.stderr.write(proc.stderr.decode(errors="replace"))
            _, rec = _last_json_line(
                proc.stdout.decode(errors="replace")
            )
            if rec is None:
                rec = {"skipped": f"mixed child rc={proc.returncode}, "
                                  "no JSON"}
            main_rec["mixed"] = rec
        except subprocess.TimeoutExpired:
            main_rec["mixed"] = {"skipped": "mixed child hit the wall "
                                            "deadline"}
    lines[main_i] = json.dumps(main_rec)
    return "\n".join(lines) + "\n"


def _with_scale_point(out: str, env: dict, t_start: float,
                      wall_cap: float) -> str:
    """Runs the scale-point child inside what's left of the wall cap
    and embeds its record under "scale" in the main JSON line.  Any
    failure leaves the main line untouched — the first metric must
    never be hostage to the second."""
    import subprocess

    if os.environ.get("JEPSEN_BENCH_SCALE_OPS", "") == "0":
        return out
    lines = out.splitlines()
    main_i, main_rec = _last_json_line(out)
    if main_rec is None or main_rec.get("value", 0) <= 0:
        return out
    wall_left = wall_cap - (time.monotonic() - t_start)
    if wall_left < 100.0:
        main_rec["scale"] = {"skipped": "wall budget exhausted"}
    else:
        env2 = dict(
            env,
            JEPSEN_BENCH_SCALE_CHILD="1",
            JEPSEN_BENCH_RATE_HINT=str(main_rec["value"]),
            JEPSEN_BENCH_SCALE_WALL=str(wall_left - 20.0),
            JEPSEN_BENCH_SCALE_BUDGET=str(
                min(300.0, max(60.0, wall_left - 60.0))
            ),
        )
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__)],
                timeout=wall_left - 10.0, env=env2, capture_output=True,
            )
            sys.stderr.write(proc.stderr.decode(errors="replace"))
            _, rec = _last_json_line(
                proc.stdout.decode(errors="replace")
            )
            if rec is None:
                rec = {"skipped": f"scale child rc={proc.returncode}, "
                                  "no JSON"}
            main_rec["scale"] = rec
        except subprocess.TimeoutExpired:
            main_rec["scale"] = {"skipped": "scale child hit the wall "
                                            "deadline"}
    lines[main_i] = json.dumps(main_rec)
    return "\n".join(lines) + "\n"


def _with_scale_online_point(out: str, env: dict, t_start: float,
                             wall_cap: float) -> str:
    """Runs the ONLINE scale child (streaming verdict-lag metric,
    ISSUE 7) inside what's left of the wall cap and embeds its record
    under "scale_online" next to "scale" in the main JSON line.  Same
    hostage rule as the other side metrics: any failure leaves the
    main line untouched."""
    import subprocess

    if os.environ.get("JEPSEN_BENCH_SCALE_ONLINE_OPS", "") == "0":
        return out
    lines = out.splitlines()
    main_i, main_rec = _last_json_line(out)
    if main_rec is None or main_rec.get("value", 0) <= 0:
        return out
    wall_left = wall_cap - (time.monotonic() - t_start)
    if wall_left < 70.0:
        main_rec["scale_online"] = {"skipped": "wall budget exhausted"}
    else:
        env2 = dict(
            env,
            JEPSEN_BENCH_SCALE_ONLINE_CHILD="1",
            JEPSEN_BENCH_RATE_HINT=str(main_rec["value"]),
            JEPSEN_BENCH_SCALE_WALL=str(wall_left - 20.0),
            JEPSEN_BENCH_SCALE_BUDGET=str(
                min(180.0, max(40.0, wall_left - 50.0))
            ),
        )
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__)],
                timeout=wall_left - 10.0, env=env2, capture_output=True,
            )
            sys.stderr.write(proc.stderr.decode(errors="replace"))
            _, rec = _last_json_line(
                proc.stdout.decode(errors="replace")
            )
            if rec is None:
                rec = {"skipped": f"online scale child "
                                  f"rc={proc.returncode}, no JSON"}
            main_rec["scale_online"] = rec
        except subprocess.TimeoutExpired:
            main_rec["scale_online"] = {
                "skipped": "online scale child hit the wall deadline"
            }
    lines[main_i] = json.dumps(main_rec)
    return "\n".join(lines) + "\n"


def _with_fleet_point(out: str, env: dict, t_start: float,
                     wall_cap: float) -> str:
    """Runs the fleet scale child (multi-tenant sustained-capacity
    metric, ISSUE 20) inside what's left of the wall cap and embeds
    its record under "fleet" in the main JSON line.  Same hostage rule
    as the other side metrics: any failure leaves the main line
    untouched."""
    import subprocess

    if os.environ.get("JEPSEN_BENCH_FLEET_TENANTS", "") == "0":
        return out
    lines = out.splitlines()
    main_i, main_rec = _last_json_line(out)
    if main_rec is None or main_rec.get("value", 0) <= 0:
        return out
    wall_left = wall_cap - (time.monotonic() - t_start)
    if wall_left < 90.0:
        main_rec["fleet"] = {"skipped": "wall budget exhausted"}
    else:
        env2 = dict(
            env,
            JEPSEN_BENCH_FLEET_CHILD="1",
            JEPSEN_BENCH_FLEET_BUDGET=str(
                min(150.0, max(60.0, wall_left - 40.0))
            ),
        )
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__)],
                timeout=wall_left - 10.0, env=env2, capture_output=True,
            )
            sys.stderr.write(proc.stderr.decode(errors="replace"))
            _, rec = _last_json_line(
                proc.stdout.decode(errors="replace")
            )
            if rec is None:
                rec = {"skipped": f"fleet child rc={proc.returncode}, "
                                  "no JSON"}
            main_rec["fleet"] = rec
        except subprocess.TimeoutExpired:
            main_rec["fleet"] = {
                "skipped": "fleet child hit the wall deadline"
            }
    lines[main_i] = json.dumps(main_rec)
    return "\n".join(lines) + "\n"


if __name__ == "__main__":
    sys.exit(main())
