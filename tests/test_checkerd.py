"""Checker-as-a-service: the long-lived checkerd daemon.

Covers the wire protocol (framed store-block encoding and the packed
columnar binary form), verdict parity between a RemoteChecker round-trip
and the in-process IndependentChecker, cross-run cohort merging (two
concurrent runs landing in one settle cohort), per-request budget
enforcement (blown budget -> unknown, never a wrong verdict), and the
automatic in-process fallback when no daemon is reachable.
"""

import io
import threading
import time

import pytest

from conftest import free_port

from jepsen_tpu.checker.linearizable import Linearizable
from jepsen_tpu.checkerd.client import (
    CheckerdClient,
    RemoteChecker,
    wrap_remote,
)
from jepsen_tpu.checkerd.protocol import (
    F_PACKED,
    F_SUBMIT,
    ProtocolError,
    model_from_spec,
    model_to_spec,
    pack_key_frame,
    read_frame,
    unpack_key_frame,
    write_frame,
)
from jepsen_tpu.checkerd.server import make_server
from jepsen_tpu.history.core import History
from jepsen_tpu.history.packed import (
    PACKED_COLUMNS,
    pack_history,
    packed_from_bytes,
    packed_to_bytes,
)
from jepsen_tpu.models.registers import CASRegister, Register
from jepsen_tpu.parallel.independent import KV, IndependentChecker
from jepsen_tpu.parallel import independent as pind


# ---------------------------------------------------------------------
# History builders


def _reg_ops(key, pairs, start_index=0, process=0):
    """[(written, read-back), ...] -> op dicts for one register key."""
    ops = []
    i = start_index
    for wrote, read in pairs:
        ops.append({"index": i, "type": "invoke", "process": process,
                    "f": "write", "value": KV(key, wrote), "time": i})
        i += 1
        ops.append({"index": i, "type": "ok", "process": process,
                    "f": "write", "value": KV(key, wrote), "time": i})
        i += 1
        ops.append({"index": i, "type": "invoke", "process": process,
                    "f": "read", "value": KV(key, None), "time": i})
        i += 1
        ops.append({"index": i, "type": "ok", "process": process,
                    "f": "read", "value": KV(key, read), "time": i})
        i += 1
    return ops


def _mixed_history():
    """Key "good" linearizable, key "bad" reads a never-written value."""
    ops = _reg_ops("good", [(1, 1), (2, 2)])
    ops += _reg_ops("bad", [(1, 7)], start_index=len(ops), process=1)
    return History(ops)


def _in_process():
    return IndependentChecker(Linearizable(Register()))


# ---------------------------------------------------------------------
# Protocol plumbing (no daemon needed)


def test_frame_roundtrip_json_and_binary():
    buf = io.BytesIO()
    write_frame(buf, F_SUBMIT, {"run": "r1", "n-keys": 2})
    write_frame(buf, F_PACKED, b"\x00\x01binary\xff")
    write_frame(buf, F_SUBMIT, {"empty": None})
    buf.seek(0)
    assert read_frame(buf) == (F_SUBMIT, {"run": "r1", "n-keys": 2})
    assert read_frame(buf) == (F_PACKED, b"\x00\x01binary\xff")
    assert read_frame(buf) == (F_SUBMIT, {"empty": None})
    assert read_frame(buf) is None  # clean EOF


def test_frame_crc_and_truncation_rejected():
    buf = io.BytesIO()
    write_frame(buf, F_SUBMIT, {"run": "r1"})
    raw = bytearray(buf.getvalue())
    raw[-1] ^= 0xFF  # corrupt payload -> CRC mismatch
    with pytest.raises(ProtocolError):
        read_frame(io.BytesIO(bytes(raw)))
    with pytest.raises(ProtocolError):
        read_frame(io.BytesIO(buf.getvalue()[:-3]))  # torn frame


def test_key_frame_roundtrip():
    blob = pack_key_frame(42, b"payload")
    assert unpack_key_frame(blob) == (42, b"payload")


def test_packed_bytes_roundtrip():
    h = History(_reg_ops("k", [(1, 1), (2, 3)]))
    pm = Register().packed()
    p = pack_history(h, pm.encode)
    q = packed_from_bytes(packed_to_bytes(p))
    assert q.n == p.n
    for name, _ in PACKED_COLUMNS:
        assert (getattr(q, name) == getattr(p, name)).all(), name


def test_packed_bytes_validation():
    h = History(_reg_ops("k", [(1, 1)]))
    pm = Register().packed()
    blob = packed_to_bytes(pack_history(h, pm.encode))
    with pytest.raises(ValueError):
        packed_from_bytes(b"XXXX" + blob[4:])  # bad magic
    with pytest.raises(ValueError):
        packed_from_bytes(blob[:-1])  # torn column


def test_model_spec_roundtrip():
    for model in (Register(), Register(3), CASRegister(), CASRegister(5)):
        spec = model_to_spec(model)
        assert spec is not None
        back = model_from_spec(spec)
        assert type(back) is type(model)
        assert model_to_spec(back) == spec
    with pytest.raises(ValueError):
        model_from_spec({"type": "no-such-model"})


def test_unspecable_model_returns_none():
    class Weird(Register):
        pass

    assert model_to_spec(Weird()) is None


# ---------------------------------------------------------------------
# Daemon round trips


@pytest.fixture()
def daemon():
    srv = make_server("127.0.0.1", 0, batch_window_s=0.0)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        yield srv, f"127.0.0.1:{srv.server_address[1]}"
    finally:
        srv.shutdown()
        srv.server_close()
        srv.scheduler.stop()
        t.join(timeout=5)


def test_remote_verdict_parity(daemon):
    """The acceptance bar: daemon verdicts identical to in-process."""
    _, addr = daemon
    h = _mixed_history()
    test = {"name": "parity"}
    inproc = _in_process().check(test, h, {})
    remote = RemoteChecker(_in_process(), addr, run_id="parity").check(
        test, h, {})
    assert remote["valid"] == inproc["valid"] is False
    assert sorted(remote["results"]) == sorted(inproc["results"])
    for k in inproc["results"]:
        assert remote["results"][k]["valid"] == \
            inproc["results"][k]["valid"], k
    assert remote["checkerd"]["merged-runs"] == 1
    assert "bad" in remote["failures"]


def test_packed_wire_parity(daemon):
    """Binary transport: pre-packed columns yield the same verdicts."""
    _, addr = daemon
    pm = Register().packed()
    good = pack_history(History(_reg_ops("g", [(1, 1)])), pm.encode)
    bad = pack_history(History(_reg_ops("b", [(1, 9)])), pm.encode)
    with CheckerdClient(addr) as c:
        ticket = c.submit_packed(
            "packed-run", model_to_spec(Register()), [good, bad])
        res = c.wait(ticket, deadline_s=120)
    krs = res["key-results"]
    assert [kr["valid"] for kr in krs] == [True, False]


def test_two_runs_merge_into_one_cohort():
    """Two concurrent runs inside one batch window settle as one cohort
    — the cross-run amortization the daemon exists for."""
    srv = make_server("127.0.0.1", 0, batch_window_s=0.6)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    addr = f"127.0.0.1:{srv.server_address[1]}"
    try:
        barrier = threading.Barrier(2)
        results = {}

        def run(name):
            h = History(_reg_ops(f"{name}-k", [(1, 1), (2, 2)]))
            rc = RemoteChecker(
                _in_process(), addr, run_id=name, fallback=False)
            barrier.wait()
            results[name] = rc.check({"name": name}, h, {})

        threads = [threading.Thread(target=run, args=(n,))
                   for n in ("run-a", "run-b")]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
        assert sorted(results) == ["run-a", "run-b"]
        for name, res in results.items():
            assert res["valid"] is True, name
            assert res["checkerd"]["merged-runs"] == 2, name
        with CheckerdClient(addr) as c:
            stats = c.stats()
        assert stats["cohorts-merged"] >= 1
        assert stats["merge-ratio"] > 0
        assert set(stats["runs"]) >= {"run-a", "run-b"}
    finally:
        srv.shutdown()
        srv.server_close()
        srv.scheduler.stop()


def test_budget_exceeded_returns_unknown(daemon):
    """A blown request budget must degrade to unknown, never block the
    daemon or return a fabricated verdict (check_safe semantics)."""
    _, addr = daemon
    h = _mixed_history()
    res = RemoteChecker(
        _in_process(), addr, run_id="broke", fallback=False,
    ).check({"name": "broke", "checker_budget": 0}, h, {})
    assert res["valid"] == "unknown"
    assert res["checkerd"].get("budget-exceeded")
    for kr in res["results"].values():
        assert kr["valid"] == "unknown"


def test_daemon_down_falls_back_in_process():
    """No daemon listening -> RemoteChecker silently degrades to the
    wrapped in-process checker and annotates the result."""
    addr = f"127.0.0.1:{free_port()}"  # nothing listening here
    h = _mixed_history()
    res = RemoteChecker(_in_process(), addr, run_id="lonely").check(
        {"name": "lonely"}, h, {})
    assert res["valid"] is False
    assert "fallback" in res["checkerd"]
    assert "bad" in res["failures"]


def test_daemon_down_without_fallback_is_unknown():
    """fallback=False still never raises into the harness: the verdict
    degrades to unknown with the transport error recorded."""
    addr = f"127.0.0.1:{free_port()}"
    res = RemoteChecker(
        _in_process(), addr, run_id="strict", fallback=False,
    ).check({"name": "strict"}, _mixed_history(), {})
    assert res["valid"] == "unknown"
    assert "checkerd unavailable" in res["error"]


def test_wrap_remote_shapes():
    """wrap_remote converts linearizable checkers (bare or independent)
    and leaves foreign checkers alone."""
    addr = "127.0.0.1:1"
    assert isinstance(wrap_remote(_in_process(), addr), RemoteChecker)
    assert isinstance(
        wrap_remote(Linearizable(Register()), addr), RemoteChecker)

    class Other:
        def check(self, test, history, opts):
            return {"valid": True}

    other = Other()
    assert wrap_remote(other, addr) is other


def test_second_run_rides_the_warm_path(daemon):
    """Same workload twice: run 2 reuses the daemon's cached model and
    settle memo, so its server-side check time beats run 1's cold one."""
    _, addr = daemon
    pind.clear_settle_memo()
    h = _mixed_history()
    t1 = RemoteChecker(
        _in_process(), addr, run_id="cold", fallback=False,
    ).check({"name": "cold"}, h, {})
    t2 = RemoteChecker(
        _in_process(), addr, run_id="warm", fallback=False,
    ).check({"name": "warm"}, h, {})
    assert t2["valid"] == t1["valid"]
    cold = t1["checkerd"]["check-s"]
    warm = t2["checkerd"]["check-s"]
    assert warm < cold, (cold, warm)


def test_restarted_daemon_warm_starts_from_plan_cache(tmp_path):
    """The plan layer of the warm path: with --plan-cache, a daemon
    journals settled plan-node verdicts; a RESTARTED daemon (fresh
    Scheduler over the same directory) must serve the byte-identical
    resubmission from the journal, and a budget change must MISS."""
    from jepsen_tpu.plan import cache as plan_cache

    h = _mixed_history()

    def one_round(run_id, time_limit_s=None):
        plan_cache.reset_for_tests()
        srv = make_server("127.0.0.1", 0, batch_window_s=0.0,
                          plan_cache_dir=str(tmp_path))
        t = threading.Thread(target=srv.serve_forever, daemon=True)
        t.start()
        try:
            addr = f"127.0.0.1:{srv.server_address[1]}"
            base = IndependentChecker(
                Linearizable(Register(), time_limit_s=time_limit_s))
            res = RemoteChecker(
                base, addr, run_id=run_id, fallback=False,
            ).check({"name": run_id}, h, {})
            return res, srv.scheduler.stats()["plan"]
        finally:
            srv.shutdown()
            srv.server_close()
            srv.scheduler.stop()
            t.join(timeout=5)
            plan_cache.reset_for_tests()

    r1, p1 = one_round("cold")
    assert r1["valid"] is False
    memo1 = p1["cache"]["memo"]
    assert memo1["puts"] >= 1

    r2, p2 = one_round("warm")  # fresh scheduler, same directory
    assert r2["valid"] is False
    memo2 = p2["cache"]["memo"]
    assert memo2["loaded"] >= memo1["puts"]
    assert memo2["hits"] >= 1
    for k in r1["results"]:
        assert r2["results"][k]["valid"] == r1["results"][k]["valid"]

    _, p3 = one_round("budget-change", time_limit_s=7.25)
    memo3 = p3["cache"]["memo"]
    assert memo3["hits"] == 0  # budget is part of the plan identity
