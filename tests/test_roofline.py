"""Roofline observatory tests: XLA cost parsing against absent /
partial / list-shaped backends, the device-peak registry and the CPU
calibration cache, achieved-vs-peak math, the v2 profile schema (v1
records normalize, torn tails tolerated), ingest counters, the chip
forensics dossier, and the perf-regression gate's true-positive /
clean-negative contract.
"""

import json
import os
import sys

import pytest

from jepsen_tpu import telemetry
from jepsen_tpu.telemetry import profile, roofline

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tools"))

import perf_gate  # noqa: E402


@pytest.fixture(autouse=True)
def _scope(tmp_path, monkeypatch):
    """Telemetry on, profile store and roofline cache in tmp, both
    restored after — roofline tests must not touch the user cache."""
    monkeypatch.setenv(roofline.CACHE_ENV,
                       str(tmp_path / "cpu-peaks.json"))
    prior = telemetry.enabled()
    prior_store = profile.store_path()
    telemetry.enable(True)
    telemetry.reset()
    profile.set_store(str(tmp_path))
    roofline._cpu_peaks = None
    yield
    roofline._cpu_peaks = None
    profile.set_store(
        os.path.dirname(prior_store) if prior_store else None)
    telemetry.reset()
    telemetry.enable(prior)


# ------------------------------------------------------- cost parsing


def test_normalize_cost_dict_with_xla_space_key():
    got = roofline._normalize_cost(
        {"flops": 100.0, "bytes accessed": 50.0})
    assert got == {"flops": 100.0, "bytes_accessed": 50.0,
                   "transcendentals": None}


def test_normalize_cost_list_of_computations_sums():
    got = roofline._normalize_cost([
        {"flops": 10.0, "bytes accessed": 5.0},
        {"flops": 20.0, "transcendentals": 2.0},
    ])
    assert got["flops"] == 30.0
    assert got["bytes_accessed"] == 5.0
    assert got["transcendentals"] == 2.0


@pytest.mark.parametrize("raw", [None, "nope", 7, {}, [], [None, "x"],
                                 {"unrelated": 1.0},
                                 {"flops": "NaN-ish"},
                                 {"flops": -5.0}])
def test_normalize_cost_garbage_fails_open(raw):
    assert roofline._normalize_cost(raw) is None


def test_cost_analysis_backend_absent_returns_none():
    class NoSupport:
        def cost_analysis(self):
            raise NotImplementedError

        def lower(self, *a, **k):
            raise RuntimeError("no lowering either")

    assert roofline.cost_analysis(NoSupport()) is None
    # A plain object without either attribute also fails open.
    assert roofline.cost_analysis(object()) is None


def test_cost_analysis_partial_backend_via_lower():
    class Lowered:
        def cost_analysis(self):
            return {"flops": 8.0}

    class Fn:
        def cost_analysis(self):
            raise AttributeError

        def lower(self, *a, **k):
            return Lowered()

    got = roofline.cost_analysis(Fn(), 1, 2)
    assert got == {"flops": 8.0, "bytes_accessed": None,
                   "transcendentals": None}


def test_instrument_notes_cost_into_capture():
    import jax
    import jax.numpy as jnp

    fn = roofline.instrument(jax.jit(lambda a: a @ a))
    assert roofline.instrument(fn) is fn  # idempotent
    x = jnp.ones((16, 16), jnp.float32)
    with profile.capture("rooftest"):
        fn(x).block_until_ready()
    rec = profile.read(profile.store_path())[-1]
    assert rec["pass"] == "rooftest"
    assert rec["cost"]["flops"] and rec["cost"]["flops"] > 0
    assert rec["cost"]["device_calls"] >= 1


def test_instrument_cache_caps_and_recovers():
    calls = []

    class Fn:
        def __call__(self, x):
            return x

        def cost_analysis(self):
            calls.append(1)
            return {"flops": 1.0}

    fn = roofline.instrument(Fn())
    for i in range(roofline._COST_CACHE_CAP + 5):
        with profile.capture("cachetest"):
            fn(float(i))
    # Cache cleared at the cap, then refilled — never unbounded.
    assert len(fn._costs) <= roofline._COST_CACHE_CAP


# ------------------------------------------------- peaks & calibration


def test_peaks_registry_tpu_generations():
    for kind, want_flops in (("TPU v4", 275e12), ("TPU v5e", 197e12),
                             ("TPU v5 lite", 197e12),
                             ("TPU v5p", 459e12), ("TPU v6e", 918e12)):
        got = roofline.peaks_for_device(
            {"platform": "tpu", "device_kind": kind})
        assert got["peak_flops_per_s"] == want_flops, kind
        assert got["source"].startswith("tpu-registry:")


def test_peaks_unknown_platform_and_unknown_tpu_null():
    assert roofline.peaks_for_device(None)["peak_flops_per_s"] is None
    assert roofline.peaks_for_device(
        {"platform": "gpu"})["peak_flops_per_s"] is None
    got = roofline.peaks_for_device(
        {"platform": "tpu", "device_kind": "TPU v99"})
    assert got["peak_flops_per_s"] is None


def test_cpu_calibration_probe_and_disk_cache(tmp_path):
    path = os.environ[roofline.CACHE_ENV]
    got = roofline.calibrate_cpu()
    assert got["peak_flops_per_s"] > 0
    assert got["peak_bytes_per_s"] > 0
    assert os.path.exists(path)
    # Second process (memo cleared) reads the disk cache, not the probe.
    roofline._cpu_peaks = None
    planted = dict(got, peak_flops_per_s=123.0)
    with open(path, "w") as f:
        json.dump(planted, f)
    assert roofline.calibrate_cpu()["peak_flops_per_s"] == 123.0
    # force=True re-measures past both caches.
    assert roofline.calibrate_cpu(
        force=True)["peak_flops_per_s"] != 123.0


def test_cpu_cache_env_empty_disables_disk(monkeypatch, tmp_path):
    monkeypatch.setenv(roofline.CACHE_ENV, "")
    roofline._cpu_peaks = None
    got = roofline.calibrate_cpu()
    assert got["peak_flops_per_s"] > 0
    assert not os.path.exists(str(tmp_path / "cpu-peaks.json"))


# --------------------------------------------------- achieved/peak math


def test_annotate_math():
    rl = roofline.annotate(
        {"execute_s": 2.0},
        {"flops": 100.0, "bytes_accessed": 50.0},
        {"platform": "tpu", "device_kind": "TPU v4"})
    assert rl["achieved_flops_per_s"] == pytest.approx(50.0)
    assert rl["achieved_bytes_per_s"] == pytest.approx(25.0)
    assert rl["arithmetic_intensity"] == pytest.approx(2.0)
    assert rl["flops_ratio"] == pytest.approx(50.0 / 275e12)
    assert rl["bandwidth_ratio"] == pytest.approx(25.0 / 1228e9)
    assert rl["knee_intensity"] == pytest.approx(275e12 / 1228e9)
    assert rl["bound"] == "memory"  # intensity 2 << knee ~224
    assert rl["peak_source"] == "tpu-registry:v4"


def test_annotate_compute_bound_side():
    rl = roofline.annotate(
        {"execute_s": 1.0},
        {"flops": 1e9, "bytes_accessed": 1.0},
        {"platform": "tpu", "device_kind": "TPU v4"})
    assert rl["bound"] == "compute"


def test_annotate_nulls_without_cost_or_timing():
    for timing, cost in ((None, None), ({"execute_s": 1.0}, None),
                         (None, {"flops": 1.0})):
        rl = roofline.annotate(timing, cost, None)
        assert set(rl) == set(profile.ROOFLINE_NULL)
        assert rl["achieved_flops_per_s"] is None
        assert rl["bound"] is None


def test_summarize_medians_and_bound_consensus():
    recs = []
    for f in (10.0, 20.0, 30.0):
        recs.append({
            "pass": "p", "timing": {"execute_s": 1.0},
            "cost": {"flops": f, "bytes_accessed": 5.0,
                     "transcendentals": None, "device_calls": 1},
            "roofline": dict(profile.ROOFLINE_NULL,
                             achieved_flops_per_s=f,
                             flops_ratio=f / 100.0, bound="compute",
                             knee_intensity=4.0),
        })
    got = roofline.summarize(recs)["p"]
    assert got["n"] == 3
    assert got["with_cost"] == 3
    assert got["median_flops"] == 20.0
    assert got["median_achieved_flops_per_s"] == 20.0
    assert got["bound"] == "compute"


# --------------------------------------------------- v2 schema / store


def test_normalize_v1_record_fills_v2_blocks():
    v1 = {"pass": "settle", "timing": {"execute_s": 0.5}}
    out = profile.normalize(dict(v1))
    assert out["v"] == 1
    assert out["cost"] == profile.COST_NULL
    assert out["roofline"] == profile.ROOFLINE_NULL
    assert out["device"] == profile.DEVICE_NULL
    # v2 records keep their own blocks.
    v2 = profile.normalize({"v": 2, "pass": "x",
                            "cost": {"flops": 3.0}})
    assert v2["cost"]["flops"] == 3.0
    assert v2["cost"]["bytes_accessed"] is None


def test_mixed_v1_v2_store_loads(tmp_path):
    path = str(tmp_path / "mixed.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"pass": "old", "timing":
                            {"execute_s": 1.0}}) + "\n")
        f.write(json.dumps({"v": 2, "pass": "new",
                            "cost": dict(profile.COST_NULL, flops=6.0),
                            "roofline": dict(profile.ROOFLINE_NULL),
                            "device": dict(profile.DEVICE_NULL)})
                + "\n")
    recs = profile.read(path)
    assert [r["pass"] for r in recs] == ["old", "new"]
    for r in recs:
        assert "flops" in r["cost"]
        assert "achieved_flops_per_s" in r["roofline"]


def test_torn_tail_tolerated(tmp_path):
    path = str(tmp_path / "torn.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"pass": "whole"}) + "\n")
        f.write('{"pass": "torn", "timing": {"exe')  # no newline, torn
    recs = profile.read(path)
    assert [r["pass"] for r in recs] == ["whole"]


def test_device_info_per_field_fail_open(monkeypatch):
    import jax

    def boom():
        raise RuntimeError("backend gone")

    monkeypatch.setattr(jax, "devices", boom)
    info = profile._device_info()
    assert set(info) == set(profile.DEVICE_NULL)
    assert info["platform"] in (None, "cpu")


def test_capture_without_cost_writes_explicit_nulls():
    with profile.capture("bare"):
        pass
    rec = profile.read(profile.store_path())[-1]
    assert rec["v"] == profile.SCHEMA_VERSION
    assert rec["cost"]["flops"] is None
    assert rec["cost"]["device_calls"] == 0
    assert "achieved_flops_per_s" in rec["roofline"]


# ------------------------------------------------------------- ingest


def test_packed_builder_counts_ingest_ops():
    from jepsen_tpu.history.core import History
    from jepsen_tpu.history.packed import PackedBuilder

    ops = []
    for i in range(10):
        ops.append({"index": 2 * i, "type": "invoke", "process": 0,
                    "f": "write", "value": i, "time": 2 * i})
        ops.append({"index": 2 * i + 1, "type": "ok", "process": 0,
                    "f": "write", "value": i, "time": 2 * i + 1})
    b = PackedBuilder(lambda inv, comp: None)
    for op in History(ops):
        b.append(op)
    b.snapshot()
    assert telemetry.counter_value("ingest.append.ops") == 20.0
    b.finish()
    assert telemetry.counter_value("ingest.append.ops") == 20.0
    assert telemetry.counter_value("ingest.snapshots") == 1.0
    spans = telemetry.summary()["spans"]
    assert "ingest.snapshot" in spans
    assert "ingest.finish" in spans


def test_ingest_counters_survive_scoped_reset():
    telemetry.count("ingest.append.ops", 5)
    telemetry.scoped_reset()
    assert telemetry.counter_value("ingest.append.ops") == 5.0


# ------------------------------------------------------- chip dossier


def _store_records(tmp_path, name, factor=1.0):
    path = str(tmp_path / name)
    perf_gate._synthetic_store(path, slow_pass_factor=factor)
    return profile.read(path)


def test_perf_gate_clean_negative(tmp_path):
    base = _store_records(tmp_path, "base.jsonl")
    cand = _store_records(tmp_path, "cand.jsonl")
    got = perf_gate.compare(
        perf_gate.bucketize(base), perf_gate.bucketize(cand),
        noise=0.35, roofline_noise=0.6, min_delta_s=0.005, min_n=3,
        calibrate=False)
    assert got["regressions"] == []
    assert got["compared"] > 0


def test_perf_gate_planted_2x_true_positive(tmp_path):
    base = _store_records(tmp_path, "base.jsonl")
    cand = _store_records(tmp_path, "cand.jsonl", factor=2.0)
    got = perf_gate.compare(
        perf_gate.bucketize(base), perf_gate.bucketize(cand),
        noise=0.35, roofline_noise=0.6, min_delta_s=0.005, min_n=3,
        calibrate=False)
    assert got["regressions"], "planted 2x slowdown not detected"
    # Only the slow pass regresses; the control pass stays clean.
    assert {r["pass"] for r in got["regressions"]} == {"beta"}


def test_perf_gate_calibration_cancels_uniform_slowdown(tmp_path):
    base = perf_gate.bucketize(_store_records(tmp_path, "base.jsonl"))
    cand = {
        sk: dict(b, median_cost_s=b["median_cost_s"] * 3.0)
        for sk, b in base.items()
    }
    got = perf_gate.compare(
        base, cand, noise=0.35, roofline_noise=0.6,
        min_delta_s=0.005, min_n=3, calibrate=True)
    assert got["regressions"] == []
    assert got["shift"] == pytest.approx(3.0)


def test_perf_gate_roofline_ratio_regression(tmp_path):
    base = perf_gate.bucketize(_store_records(tmp_path, "base.jsonl"))
    cand = {
        sk: dict(b,
                 median_cost_s=b["median_cost_s"] * 1.2,
                 median_flops_ratio=(b.get("median_flops_ratio") or 0)
                 * 0.1)
        for sk, b in base.items()
    }
    got = perf_gate.compare(
        base, cand, noise=0.35, roofline_noise=0.6,
        min_delta_s=0.001, min_n=3, calibrate=False)
    kinds = {r["kind"] for r in got["regressions"]}
    assert "roofline" in kinds


def test_perf_gate_seed_and_load_roundtrip(tmp_path):
    recs = _store_records(tmp_path, "base.jsonl")
    path = str(tmp_path / "baseline.json")
    seeded = perf_gate.seed_baseline(recs, path)
    loaded = perf_gate.load_baseline(path)
    assert loaded == seeded
    assert loaded["v"] == perf_gate.BASELINE_VERSION
    assert all("median_cost_s" in b
               for b in loaded["buckets"].values())


def test_perf_gate_selftest_passes():
    assert perf_gate.selftest() == 0
