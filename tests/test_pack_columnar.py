"""pack_history's columnar pass against the per-op reference.

`PackedBuilder.append` + `finish` pairs and encodes one op at a time;
pack_history pairs the whole history in numpy and encodes the rows in
one batch.  Each side gets a fresh encoder of the same model, so the
interner's codes (a0/a1) are compared too: they depend on the order in
which rows reach the encoder.
"""

import numpy as np
import pytest

from jepsen_tpu import telemetry
from jepsen_tpu.checker.linearizable import Linearizable
from jepsen_tpu.history import packed
from jepsen_tpu.history.core import Op, history
from jepsen_tpu.history.packed import (
    PackedBuilder,
    pack_history,
    packed_to_bytes,
)
from jepsen_tpu.models import cas_register, multi_register, unordered_queue
from jepsen_tpu.utils.histgen import random_register_history


def per_op(h, pm):
    b = PackedBuilder(pm.encode)
    for o in h:
        b.append(o)
    return packed_to_bytes(b.finish())


def columnar(h, pm):
    return packed_to_bytes(pack_history(h, pm.encode))


@pytest.fixture(params=["columnar", "adaptive"])
def path(request, monkeypatch):
    """`columnar` takes the columnar pass at every size; `adaptive`
    keeps the per-op path below `_PACK_MIN` client events."""
    if request.param == "columnar":
        monkeypatch.setattr(packed, "_PACK_MIN", 0)
    return request.param


@pytest.mark.parametrize("seed", range(12))
def test_fuzzed_register_histories(path, seed):
    rng = np.random.default_rng(seed)
    h = random_register_history(
        int(rng.integers(1, 1200)),
        procs=int(rng.integers(1, 8)),
        info_rate=float(rng.uniform(0, 0.3)),
        seed=int(rng.integers(0, 1 << 31)),
    )
    assert columnar(h, cas_register().packed()) == \
        per_op(h, cas_register().packed())


def test_cas_register_100k():
    h = random_register_history(100_000, procs=16, info_rate=0.05, seed=1)
    assert len(h) >= packed._PACK_MIN
    assert columnar(h, cas_register().packed()) == \
        per_op(h, cas_register().packed())


def _w(p, v, t="invoke"):
    return Op(type=t, f="write", value=v, process=p)


def _pad(n, p=9):
    """n completed writes of value 0 on process p."""
    return [o for _ in range(n) for o in (_w(p, 0), _w(p, 0, "ok"))]


EDGES = {
    "double-invoke": [_w(0, 1), _w(0, 2), _w(0, 2, "ok")],
    "double-invoke-unfinished": [_w(0, 1), _w(1, 5), _w(0, 2), _w(1, 5, "ok")],
    "triple-invoke": [_w(3, 1), _w(3, 2), _w(3, 3), _w(3, 3, "ok")],
    "completion-without-invocation": [_w(1, 3, "ok"), _w(1, 4), _w(1, 4, "ok")],
    "fail": [_w(1, 4), _w(1, 4, "fail"), _w(2, 6), _w(2, 6, "ok")],
    "info": [_w(1, 4), _w(1, 4, "info"), _w(2, 6), _w(2, 6, "ok")],
    "nemesis": [_w(0, 1), Op(type="invoke", f="kill", process="nemesis"),
                _w(0, 1, "ok"), Op(type="info", f="kill", process="nemesis")],
    "empty": [],
    "only-nemesis": [Op(type="invoke", f="kill", process="nemesis")],
    # Codes follow the order rows are emitted, not process order.
    "high-process-writes-first": [_w(7, 40), _w(0, 41), _w(7, 40, "ok"),
                                  _w(0, 41, "ok")],
    # Unfinished rows come last, in the order their processes entered
    # the pending dict: 3 (first at 20, then 22), then 0.
    "unfinished-order": [_w(3, 20), _w(0, 21), _w(3, 22), _w(5, 1),
                         _w(5, 1, "ok")],
    "read-dropped": [Op(type="invoke", f="read", process=2),
                     Op(type="info", f="read", process=2),
                     Op(type="invoke", f="read", process=1),
                     Op(type="ok", f="read", value=None, process=1),
                     Op(type="invoke", f="read", process=4)],
}


@pytest.mark.parametrize("name", sorted(EDGES))
def test_edge_pairings(path, name):
    ops = EDGES[name]
    # Padding on a process of its own puts the columnar run past
    # _PACK_MIN in the adaptive path too.
    for h in (history(ops), history(ops + _pad(packed._PACK_MIN))):
        assert columnar(h, cas_register().packed()) == \
            per_op(h, cas_register().packed())


def test_encoder_without_batched_form(path):
    """multi-register has no `encode.many`: one encode call per row."""
    rng = np.random.default_rng(5)
    ops = []
    for i in range(400):
        p = int(rng.integers(0, 6))
        k, v = str(rng.integers(0, 3)), int(rng.integers(0, 5))
        f = "write" if rng.random() < 0.5 else "read"
        ops.append(Op(type="invoke", f=f, process=p,
                      value=(k, v if f == "write" else None)))
        t = rng.choice(["ok", "ok", "ok", "info", "fail"])
        ops.append(Op(type=str(t), f=f, process=p, value=(k, v)))
    h = history(ops)
    model = multi_register({"0": 0, "1": 0, "2": 0})
    assert not hasattr(model.packed().encode, "many")
    assert columnar(h, multi_register({"0": 0, "1": 0, "2": 0}).packed()) \
        == per_op(h, multi_register({"0": 0, "1": 0, "2": 0}).packed())


def _queue_history(n):
    ops = []
    for i in range(n):
        ops += [Op(type="invoke", f="enqueue", value=i, process=0),
                Op(type="ok", f="enqueue", value=i, process=0),
                Op(type="invoke", f="dequeue", value=None, process=1),
                Op(type="ok", f="dequeue", value=i, process=1)]
    # An indeterminate dequeue has no packed form.
    ops += [Op(type="invoke", f="dequeue", value=None, process=2),
            Op(type="info", f="dequeue", value=None, process=2)]
    return history(ops)


def test_value_error_reaches_host_fallback(path):
    h = _queue_history(packed._PACK_MIN // 4 + 1)
    with pytest.raises(ValueError):
        pack_history(h, unordered_queue().packed().encode)
    out = Linearizable(unordered_queue(), "wgl-tpu").check({}, h, {})
    assert out["valid"] is True
    assert "unpackable" in out["algorithm"]


@pytest.mark.parametrize("value", [2 ** 31, -(2 ** 31) - 1, 2 ** 70])
def test_overflow_past_int32(path, value):
    def encode(inv, comp):
        return (0, value, 0)

    ops = [o for i in range(300) for o in (_w(i % 4, 1), _w(i % 4, 1, "ok"))]
    with pytest.raises(OverflowError):
        pack_history(history(ops), encode)


def test_counters_rows_and_scalar():
    prev = telemetry.enabled()
    telemetry.enable(True)
    try:
        def read():
            return (telemetry.counter_value("ingest.pack.rows"),
                    telemetry.counter_value("ingest.pack.scalar"))

        big = random_register_history(2000, procs=8, info_rate=0.05, seed=3)
        rows0, scalar0 = read()
        p = pack_history(big, cas_register().packed().encode)
        assert read() == (rows0 + p.n, scalar0)
        small = random_register_history(50, procs=4, seed=4)
        q = pack_history(small, cas_register().packed().encode)
        assert read() == (rows0 + p.n + q.n, scalar0 + 1)
        r = pack_history(big, lambda inv, comp: (1, 0, 0))
        assert read() == (rows0 + p.n + q.n + r.n, scalar0 + 2)
    finally:
        telemetry.enable(prev)


def test_append_many_keeps_pending_dict_order():
    """A process pending across chunks whose next chunk holds only
    invocations keeps its place in the pending dict, so finish()
    encodes its unfinished write before a later-pending process's."""
    a = _pad(8) + [_w(2, 101), _w(5, 100)]
    b = [_w(2, 103)] + _pad(8, p=8)
    h = history(a + b)
    ops = list(h)
    builder = PackedBuilder(cas_register().packed().encode)
    builder.append_many(ops[:len(a)])
    builder.append_many(ops[len(a):])
    assert packed_to_bytes(builder.finish()) == \
        per_op(h, cas_register().packed())
