"""The invalid-heavy jepsen.independent shape through the cohort
settling ladder (the plan executor's cohort plan, plan/compiler.py
run_cohort).

The bar is DIFFERENTIAL: the fast path (stream witness -> memo ->
refutation screens -> batched BFS -> parallel CPU settle) must produce
verdicts identical to per-key exact checking — same overall verdict,
same counterexample keys, same per-key valid — on a mixed workload
where ~15% of keys carry a planted violation.  The memoization and
segment-kill mechanics get their own targeted tests.
"""

import pytest

from jepsen_tpu import telemetry
from jepsen_tpu.checker.linearizable import Linearizable
from jepsen_tpu.history.core import history as make_history
from jepsen_tpu.history.packed import pack_history
from jepsen_tpu.models import cas_register
from jepsen_tpu.ops.wgl_stream import check_wgl_witness_stream
from jepsen_tpu.parallel.independent import (
    IndependentChecker,
    clear_settle_memo,
    kv,
)
from jepsen_tpu.parallel.mesh import default_mesh
from jepsen_tpu.utils.histgen import random_register_history


def _mixed_history(n_keys, n_ops, bad_keys, procs=4, info=0.05):
    ops = []
    for i in range(n_keys):
        h = random_register_history(
            n_ops, procs=procs, info_rate=info, seed=i,
            bad=(i in bad_keys),
        )
        ops += [o.replace(value=kv(f"k{i}", o.value)) for o in h]
    return make_history(ops)


def _assert_verdict_parity(n_keys, n_ops, bad_keys):
    hist = _mixed_history(n_keys, n_ops, bad_keys)
    test = {"mesh": default_mesh(8)}
    clear_settle_memo()

    fast = IndependentChecker(
        Linearizable(cas_register(), time_limit_s=600.0)
    ).check(test, hist, {})
    # The reference per-key exact path: an explicitly-named engine
    # skips every device tier and checks each key on the CPU.
    exact = IndependentChecker(
        Linearizable(cas_register(), "cpu", time_limit_s=600.0)
    ).check(test, hist, {})

    assert fast["valid"] == exact["valid"]
    assert fast["failure-count"] == exact["failure-count"] == \
        len(bad_keys)
    assert sorted(fast["failures"]) == sorted(exact["failures"])
    for k, er in exact["results"].items():
        assert fast["results"][k]["valid"] == er["valid"], (
            k, fast["results"][k], er,
        )


def test_mixed_verdict_parity_small():
    _assert_verdict_parity(40, 60, bad_keys={3, 11, 17, 24, 30, 38})


@pytest.mark.slow
def test_mixed_verdict_parity_bench_shape():
    """The benchmarked shape itself: 200 keys x 100 ops, 15% bad."""
    _assert_verdict_parity(200, 100, bad_keys=set(range(30)))


def test_settle_memo_shares_verdicts_across_identical_keys():
    """Three keys carrying byte-identical bad subhistories settle ONCE:
    one representative runs the ladder, the others replay its verdict
    (wgl.settle.memo-hit) — and every replica still reports invalid."""
    bad = random_register_history(60, procs=4, info_rate=0.05,
                                  seed=7, bad=True)
    good = random_register_history(60, procs=4, info_rate=0.05, seed=8)
    ops = []
    for name in ("a", "a2", "a3"):  # identical bad subhistory x3
        ops += [o.replace(value=kv(name, o.value)) for o in bad]
    ops += [o.replace(value=kv("g", o.value)) for o in good]
    hist = make_history(ops)

    clear_settle_memo()
    telemetry.enable(True)
    telemetry.reset()
    try:
        res = IndependentChecker(
            Linearizable(cas_register(), time_limit_s=600.0)
        ).check({"mesh": default_mesh(8)}, hist, {})
        counters = telemetry.settle_counters()
    finally:
        telemetry.enable(False)

    assert res["valid"] is False
    assert sorted(res["failures"]) == ["a", "a2", "a3"]
    for k in ("a", "a2", "a3"):
        assert res["results"][k]["valid"] is False
    assert counters.get("wgl.settle.memo-hit", 0) >= 2, counters


def test_settle_memo_never_shares_positional_certificates():
    """A memo-shared verdict must not cite another key's certificate:
    the positional fields stay with the representative only."""
    bad = random_register_history(60, procs=4, info_rate=0.05,
                                  seed=7, bad=True)
    ops = []
    for name in ("a", "b"):
        ops += [o.replace(value=kv(name, o.value)) for o in bad]
    hist = make_history(ops)
    clear_settle_memo()
    res = IndependentChecker(
        Linearizable(cas_register(), time_limit_s=600.0)
    ).check({"mesh": default_mesh(8)}, hist, {})
    shared = [r for r in res["results"].values() if r.get("memo-hit")]
    assert shared, res["results"]
    for r in shared:
        assert r["valid"] is False
        for field in ("final-configs", "crashed-op",
                      "counterexample-file"):
            assert field not in r, r


def test_stream_segment_kill_bounds_the_blast_radius():
    """One bad key kills only its segment's remainder: with
    segment_keys=4, the valid keys in OTHER segments (and before the
    bad key in its own) still prove True in bounded restarts."""
    pm = cas_register().packed()
    bad_keys = {5, 13}
    packs = []
    for i in range(20):
        h = random_register_history(80, procs=4, info_rate=0.05,
                                    seed=100 + i, bad=(i in bad_keys))
        packs.append(pack_history(h, pm.encode))

    v = check_wgl_witness_stream(packs, pm, segment_keys=4)
    for i in range(20):
        if i in bad_keys:
            assert v[i] is not True, i
        else:
            assert v[i] is True, i


def test_settle_algorithm_screens_before_search():
    """The "settle" engine refutes a planted violation through the
    O(n log n) screens (checker/refute.py) without touching the
    exponential search — the property the cohort ladder's speed rests
    on."""
    h = random_register_history(100, procs=4, info_rate=0.05,
                                seed=3, bad=True)
    res = Linearizable(cas_register(), "settle",
                       time_limit_s=60.0).check({}, make_history(h), {})
    assert res["valid"] is False
    assert res["algorithm"] == "refute-screen", res


def test_settle_algorithm_proves_valid_histories():
    """When the screens have no opinion (the history is actually
    linearizable), "settle" falls through to the exact engine and
    proves it."""
    h = random_register_history(60, procs=4, info_rate=0.05, seed=4)
    res = Linearizable(cas_register(), "settle",
                       time_limit_s=60.0).check({}, make_history(h), {})
    assert res["valid"] is True, res
