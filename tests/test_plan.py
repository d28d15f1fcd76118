"""The checking-plan subsystem: IR, cost model, persistent caches.

The invalidation contract is the load-bearing part: a journaled
plan-node verdict may only be served for a byte-identical resubmission
— same packed digest AND same plan identity (model spec, budget,
algorithm).  Changing any one of those must MISS; serving a stale
verdict across any of them would be a soundness bug, not a perf bug.
"""

import json
import os

import pytest

from jepsen_tpu import telemetry
from jepsen_tpu.checker.linearizable import Linearizable
from jepsen_tpu.history.core import History
from jepsen_tpu.models.registers import CASRegister, Register
from jepsen_tpu.parallel.independent import KV, IndependentChecker
from jepsen_tpu.plan import cache as plan_cache
from jepsen_tpu.plan import costmodel
from jepsen_tpu.plan.compiler import _identity, compile_cohort_plan
from jepsen_tpu.plan.ir import PassFamily, PassNode, Plan, known_families


@pytest.fixture(autouse=True)
def _clean_cache_state():
    plan_cache.reset_for_tests()
    costmodel.set_model_path(None)
    yield
    plan_cache.reset_for_tests()
    costmodel.set_model_path(None)


# ---------------------------------------------------------------------
# IR


def test_plan_ir_shapes_and_fingerprint():
    a = PassNode("a", "stream-witness", knobs={"segment": 4},
                 edges={"unknown": "b"})
    b = PassNode("b", "settle-exact", group=True)
    p = Plan([a, b], meta={"kind": "test"})
    assert list(p.nodes) == ["a", "b"]
    assert a.target("unknown") == "b"
    # Unlabelled edges fall back to the unknown edge.
    assert a.target("refuted") == "b"
    f1 = p.fingerprint()
    p2 = Plan([PassNode("a", "stream-witness", knobs={"segment": 8},
                        edges={"unknown": "b"}),
               PassNode("b", "settle-exact", group=True)])
    assert f1 != p2.fingerprint()  # knobs are part of the identity
    assert f1 == Plan([a, b], meta={"kind": "test"}).fingerprint()


def test_plan_rejects_backward_and_dangling_edges():
    with pytest.raises(ValueError):
        Plan([PassNode("a", "stream-witness",
                       edges={"unknown": "missing"})])
    b = PassNode("b", "settle-exact", edges={"unknown": "a"})
    with pytest.raises(ValueError):
        Plan([PassNode("a", "stream-witness"), b])  # backward edge


def test_builtin_families_registered():
    fams = known_families()
    for name in ("stream-witness", "refute-screen", "batched-bfs",
                 "settle-exact", "persistent-memo", "device-ladder",
                 "packs-exact", "elle-cycles"):
        assert name in fams, name


def test_pass_family_validation():
    with pytest.raises(ValueError):
        PassFamily("x", "sometimes-right", "device", lambda *a: None)
    with pytest.raises(ValueError):
        PassFamily("x", "exact", "quantum", lambda *a: None)


# ---------------------------------------------------------------------
# Persistent memo: invalidation semantics


def _lin(**kw):
    return Linearizable(Register(), **kw)


def _ident(lin, model=None):
    return _identity(lin, (model or Register()).packed(), "cohort")


def test_memo_key_misses_on_any_identity_change():
    lin = _lin()
    digest = "d" * 64
    base = plan_cache.memo_key(digest, _ident(lin))
    # Byte-identical resubmission -> same key (HIT).
    assert plan_cache.memo_key(digest, _ident(_lin())) == base
    # Model spec change -> MISS.
    assert plan_cache.memo_key(
        digest, _identity(lin, CASRegister().packed(), "cohort")) != base
    # Budget change -> MISS.
    assert plan_cache.memo_key(
        digest, _ident(_lin(time_limit_s=5.0))) != base
    # Algorithm change -> MISS.
    assert plan_cache.memo_key(
        digest, _ident(_lin(algorithm="linear"))) != base
    # Packed-digest change -> MISS.
    assert plan_cache.memo_key("e" * 64, _ident(lin)) != base
    # Mode kind change -> MISS (cohort verdicts never serve packs).
    assert plan_cache.memo_key(
        digest, _identity(lin, Register().packed(), "packs")) != base


def test_plan_memo_journal_roundtrip_and_warm_load(tmp_path):
    path = str(tmp_path / "plan-memo.jtpu")
    m1 = plan_cache.PlanMemo(path)
    assert m1.get("k1") is None  # miss
    m1.put("k1", {"valid": True, "algorithm": "wgl-tpu-stream"})
    m1.put("k2", {"valid": False, "algorithm": "settle"})
    got = m1.get("k1")
    assert got == {"valid": True, "algorithm": "wgl-tpu-stream"}
    got["valid"] = "mutated"  # caller-owned copy, store unaffected
    assert m1.get("k1")["valid"] is True
    m1.close()

    m2 = plan_cache.PlanMemo(path)  # fresh process stand-in
    assert m2.loaded == 2
    assert m2.get("k2") == {"valid": False, "algorithm": "settle"}
    m2.close()


def test_plan_memo_survives_torn_tail(tmp_path):
    path = str(tmp_path / "plan-memo.jtpu")
    m1 = plan_cache.PlanMemo(path)
    m1.put("k1", {"valid": True, "algorithm": "a"})
    m1.put("k2", {"valid": True, "algorithm": "b"})
    m1.close()
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) - 3)  # tear the last block
    m2 = plan_cache.PlanMemo(path)
    assert m2.get("k1") == {"valid": True, "algorithm": "a"}
    assert m2.get("k2") is None  # torn entry dropped, not corrupted
    # The journal must accept appends after truncation.
    m2.put("k3", {"valid": False, "algorithm": "c"})
    m2.close()
    m3 = plan_cache.PlanMemo(path)
    assert m3.get("k3") == {"valid": False, "algorithm": "c"}
    m3.close()


def test_memo_skips_oversize_and_duplicate_puts(tmp_path):
    m = plan_cache.PlanMemo(str(tmp_path / "m.jtpu"))
    m.put("k", {"valid": True, "blob": "x" * (plan_cache.MAX_ENTRY_BYTES + 1)})
    assert m.get("k") is None
    m.put("k", {"valid": True})
    m.put("k", {"valid": False})  # first write wins; no overwrite
    assert m.get("k") == {"valid": True}
    assert m.puts == 1
    m.close()


# ---------------------------------------------------------------------
# End-to-end MISS/HIT through the checker


def _history(read_back=2):
    ops = []

    def add(f, key, value):
        i = len(ops)
        ops.append({"index": i, "type": "invoke", "process": 0, "f": f,
                    "value": KV(key, None if f == "read" else value),
                    "time": i})
        ops.append({"index": i + 1, "type": "ok", "process": 0, "f": f,
                    "value": KV(key, value), "time": i + 1})

    add("write", "k", 2)
    add("read", "k", read_back)
    return History(ops)


def test_checker_hits_memo_only_on_identical_resubmission(
        tmp_path, monkeypatch):
    monkeypatch.setenv("JEPSEN_PLAN_CACHE", str(tmp_path))
    telemetry.enable(True)

    def run(lin):
        from jepsen_tpu.parallel import independent as pind

        pind.clear_settle_memo()
        return IndependentChecker(lin).check(
            {"name": "t"}, _history(), {"history-key": None})

    r1 = run(_lin())
    assert r1["valid"] is True
    memo = plan_cache.active_memo()
    puts_after_cold = memo.stats()["puts"]
    assert puts_after_cold >= 1

    hits0 = memo.stats()["hits"]
    r2 = run(_lin())  # byte-identical -> HIT
    assert r2["valid"] is True
    assert memo.stats()["hits"] > hits0

    hits1 = memo.stats()["hits"]
    r3 = run(_lin(time_limit_s=7.5))  # budget change -> MISS
    assert r3["valid"] is True
    assert memo.stats()["hits"] == hits1

    r4 = run(Linearizable(CASRegister()))  # model change -> MISS
    assert r4["valid"] is True
    assert memo.stats()["hits"] == hits1


# ---------------------------------------------------------------------
# Cost model


def test_untrained_choosers_equal_legacy_formulas():
    for k in (1, 7, 8, 60, 2000):
        knobs, src = costmodel.choose_stream_knobs(k, 100 * k, model=None)
        assert src == "heuristic"
        assert knobs == {"segment": max(8, -(-k // 8)),
                         "max_restarts": max(8, k // 2)}
    knobs, src = costmodel.choose_batched_knobs(10, 1000, 48, model=None)
    assert (knobs, src) == ({"beam": 32}, "heuristic")
    assert costmodel.choose_tier_order(10, 1000, knobs, model=None) \
        == "stream-first"


def test_fit_predict_and_support_clamping():
    rows = []
    for seg, cost in ((2, 0.14), (4, 0.08), (8, 0.12), (16, 0.12)):
        for jitter in (0.0, 0.002, -0.002):
            rows.append({
                "pass": "stream",
                "features": {"keys": 60, "ops": 14000},
                "plan": {"segment": seg, "max_restarts": 30},
                "timing": {"execute_s": cost + jitter},
            })
    model = costmodel.fit(rows, min_samples=4)
    assert model.has("stream")
    sup = model.passes["stream"]["support"]
    assert sup["segment"] == [2.0, 16.0]
    knobs, src = costmodel.choose_stream_knobs(60, 14000, model=model)
    assert src == "model"
    # Chosen knobs must sit inside the trained support.
    assert 2 <= knobs["segment"] <= 16
    assert knobs["max_restarts"] == 30
    # A shape whose candidates all fall outside support -> heuristics.
    knobs, src = costmodel.choose_stream_knobs(4000, 9e6, model=model)
    assert src == "heuristic"


def test_model_file_roundtrip_and_graceful_failure(tmp_path):
    rows = [{"pass": "stream", "features": {"keys": 10, "ops": 100},
             "plan": {"segment": s, "max_restarts": 8},
             "timing": {"total_s": 0.01 * s}} for s in (2, 4, 8, 16)]
    model = costmodel.fit(rows, min_samples=4)
    path = str(tmp_path / "m.json")
    model.save(path)
    loaded = costmodel.CostModel.load(path)
    assert loaded is not None and loaded.has("stream")
    assert costmodel.CostModel.load(str(tmp_path / "nope.json")) is None
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert costmodel.CostModel.load(str(bad)) is None
    vbad = tmp_path / "vbad.json"
    vbad.write_text(json.dumps({"v": 999, "passes": {}}))
    assert costmodel.CostModel.load(str(vbad)) is None


# ---------------------------------------------------------------------
# Compiler shape


def test_cohort_plan_mirrors_legacy_ladder_order():
    lin = _lin()
    plan, entry = compile_cohort_plan(
        _FakeChecker(), {}, lin, Register().packed(),
        60, 6000, has_unpackable=True)
    ids = list(plan.nodes)
    assert ids[0] == "fallback"
    assert entry == "router"
    # The settle group tail preserves ladder order.
    assert ids[-3:] == ["screen", "batched", "detail"]
    assert plan.nodes["screen"].target("refuted") == "detail"
    assert plan.nodes["screen"].target("unknown") == "batched"
    assert plan.nodes["batched"].target("unknown") == "detail"
    # Untrained: knobs are exactly the legacy formulas.
    assert plan.nodes["stream"].knobs == {"segment": 8,
                                          "max_restarts": 30}
    assert plan.nodes["batched"].knobs == {"beam": 32}
    assert plan.meta["knobs"] == "heuristic"


class _FakeChecker:
    streaming = True
    bound = None


# ---------------------------------------------------------------------
# Executor errors: an honest unknown, no second engine


def _single_history():
    """One register: write 1, read it back (linearizable)."""
    ops = []
    for f, v in (("write", 1), ("read", 1)):
        i = len(ops)
        ops.append({"index": i, "type": "invoke", "process": 0, "f": f,
                    "value": None if f == "read" else v, "time": i})
        ops.append({"index": i + 1, "type": "ok", "process": 0, "f": f,
                    "value": v, "time": i + 1})
    return History(ops)


def _via_linearizable():
    from jepsen_tpu.checker.core import check_safe

    r = check_safe(_lin(), {"name": "t"}, _single_history(), {})
    return r, [r]


def _via_independent():
    from jepsen_tpu.checker.core import check_safe

    r = check_safe(IndependentChecker(_lin()), {"name": "t"},
                   _history(), {})
    return r, [r]


def _via_checkerd_packed():
    import threading

    from jepsen_tpu.checkerd.client import CheckerdClient
    from jepsen_tpu.checkerd.protocol import model_to_spec
    from jepsen_tpu.checkerd.server import make_server
    from jepsen_tpu.history.packed import pack_history

    packed = pack_history(_single_history(), Register().packed().encode)
    srv = make_server("127.0.0.1", 0, batch_window_s=0.0)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        with CheckerdClient(f"127.0.0.1:{srv.server_address[1]}") as c:
            ticket = c.submit_packed(
                "plan-error", model_to_spec(Register()), [packed])
            r = c.wait(ticket, deadline_s=120)
    finally:
        srv.shutdown()
        srv.server_close()
        srv.scheduler.stop()
        t.join(timeout=5)
    return r, r["key-results"]


@pytest.mark.parametrize("route", [
    _via_linearizable, _via_independent, _via_checkerd_packed,
], ids=["linearizable", "independent", "checkerd-packed"])
def test_executor_error_is_an_honest_unknown(route, monkeypatch):
    """An exception out of the plan executor reaches the caller's own
    handler as `unknown` with the error: no other engine decides the
    check instead.  The compiler binds `execute` by name, so that is
    the name to break."""
    from jepsen_tpu.parallel import independent as pind
    from jepsen_tpu.plan import compiler

    def broken(*_a, **_kw):
        raise RuntimeError("executor broke")

    monkeypatch.setattr(compiler, "execute", broken)
    pind.clear_settle_memo()
    was_on = telemetry.enabled()
    telemetry.enable(True)
    telemetry.reset()
    try:
        r, key_results = route()
        summ = telemetry.summary()
    finally:
        telemetry.reset()
        telemetry.enable(was_on)
    assert r["valid"] == "unknown", r
    assert key_results
    for kr in key_results:
        assert kr["valid"] == "unknown", kr
        assert "executor broke" in kr["error"], kr
    ran = [s for s in summ["spans"]
           if s == "wgl.witness" or s.startswith("wgl.stream")]
    assert ran == [], ran
    settled = {k: v for k, v in summ["counters"].items()
               if k.startswith("wgl.settle.") and v}
    assert settled == {}, settled
