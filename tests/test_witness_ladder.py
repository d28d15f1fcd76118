"""The witness's window ladder (`ops/wgl.py` `check_wgl_device`): the
narrow rung, the re-plan of its drops, the wide rung, and the spans and
counters that say which ran.

Histories come from the benchmark's partition family
(`benchmark/families/register_partition.py`): the :info ops of a
partition nemesis, bunched in the partitioned phases, and a fresh
process id after every crash.  On the CPU the narrow window is patched
down so that the ladder runs at small sizes; one case escalates with the
real constants.  Verdicts are compared with `checker/wgl_cpu.py` and
with the benchmark's plain reference."""

import json
import os

import pytest

from benchmark.families import register, register_partition as rp
from jepsen_tpu import telemetry
from jepsen_tpu.checker.wgl_cpu import check_wgl_cpu
from jepsen_tpu.history.packed import pack_history
from jepsen_tpu.models import cas_register
from jepsen_tpu.ops import wgl_witness

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "cas-register-100k-partition.json")) as _f:
    CONFIG = json.load(_f)

#: The narrow rung's info window in the patched cases: below the :info
#: count of every history they use, so the narrow plan drops columns.
NARROW = 4
WIDE = wgl_witness.WIDE_INFO_WINDOW


def partition_entry(ops, phase_ops, seed, planted=None):
    """One single-key history of the partition family at `ops` ops.
    `planted`: None (valid), "unsupported" (a read of a value nothing
    writes, at the end) or "hidden" (`register.hidden_violation` at the
    front, which no screen refutes)."""
    config = {**CONFIG, "ops_per_key": ops, "phase_ops": phase_ops}
    traffic = {"pool": 1, "bad_key_share": float(planted == "unsupported")}
    entry = rp.generate(config, traffic, seed)[0]
    if planted == "hidden":
        entry.keys[0] = register.hidden_violation(
            entry.keys[0], CONFIG["values"], 3, (3, 4))
    entry.program_input = rp.to_program(config, entry)
    return config, entry


@pytest.fixture
def rungs(monkeypatch):
    """Every witness rung `check_wgl_device` runs, in order, as
    (info window, found a witness)."""
    seen = []
    inner = wgl_witness.check_wgl_witness

    def recorded(packed, pm, **kw):
        res = inner(packed, pm, **kw)
        seen.append((kw["info_window"], res is not None))
        return res

    monkeypatch.setattr(wgl_witness, "check_wgl_witness", recorded)
    return seen


@pytest.fixture
def traced():
    prior = telemetry.enabled()
    telemetry.reset()
    telemetry.enable(True)
    yield
    telemetry.enable(prior)
    telemetry.reset()


@pytest.fixture
def narrow(monkeypatch):
    monkeypatch.setattr(wgl_witness, "NARROW_INFO_WINDOW", NARROW)


def assert_traced_as_run(seen):
    """The ladder's spans and counters match the rungs that ran: a
    narrow span per narrow rung, a miss and a re-plan per narrow rung
    that found nothing, and a wide span and a `widened` count exactly
    per wide rung."""
    s = telemetry.summary()
    spans = {k: v["count"] for k, v in s["spans"].items()}
    counters = s["counters"]
    wide = sum(w == WIDE for w, _ in seen)
    misses = sum(w != WIDE and not hit for w, hit in seen)
    assert spans.get("wgl.witness.narrow", 0) == len(seen) - wide
    assert counters.get("wgl.witness.narrow-miss", 0) == misses
    assert spans.get("wgl.witness.plan-drops", 0) == misses
    assert counters.get("wgl.witness.widened", 0) == wide
    assert spans.get("wgl.witness.wide", 0) == wide


@pytest.mark.parametrize("ops,phase_ops,seed,planted", [
    (1200, 200, 1, None),
    (2000, 250, 3, None),
    (1200, 200, 5, "hidden"),
    (2000, 250, 6, "hidden"),
    (80, 20, 6, "unsupported"),
])
def test_partition_histories_agree_under_the_ladder(
        ops, phase_ops, seed, planted, narrow, rungs, traced):
    """`core.analyze`, `wgl_cpu` and the reference give one verdict.
    A valid history is decided by the wide rung after the narrow one
    dies; on an invalid one no rung returns a witness, whether it runs
    after the screens or on its own."""
    pm = cas_register().packed()
    config, entry = partition_entry(ops, phase_ops, seed, planted)
    got = rp.System(config).check(entry)["valid"]
    packed = pack_history(entry.program_input, pm.encode)
    cpu = check_wgl_cpu(packed, pm, max_configs=2_000_000).valid
    ref = rp.reference_verdicts(entry)[0]
    assert got == cpu == ref == (planted is None)
    assert_traced_as_run(rungs)
    if planted is None:
        assert rungs == [(NARROW, False), (WIDE, True)]
        return
    # A host screen refutes the unsupported read before any rung runs.
    assert rungs == ([] if planted == "unsupported"
                     else [(NARROW, False), (WIDE, False)])
    assert wgl_witness.plan_drops(packed, info_window=NARROW)
    for window in (NARROW, WIDE):
        assert wgl_witness.check_wgl_witness(
            packed, pm, info_window=window) is None


@pytest.mark.parametrize("ops,phase_ops,seed,planted,ladder", [
    # Few :info ops: the narrow rung decides.
    (1200, 200, 1, None, [(wgl_witness.NARROW_INFO_WINDOW, True)]),
    # The narrow rung dies but dropped nothing: no wide retry.
    (1200, 200, 5, "hidden", [(wgl_witness.NARROW_INFO_WINDOW, False)]),
    # One partitioned phase of 5,000 ops bunches 1,483 :info ops: the
    # narrow rung dies and the wide one decides.
    (10_000, 5000, 1, None,
     [(wgl_witness.NARROW_INFO_WINDOW, False), (WIDE, True)]),
])
def test_ladder_with_the_real_windows(ops, phase_ops, seed, planted, ladder,
                                      rungs, traced):
    """With the shipped constants the ladder widens only when the
    narrow plan dropped columns and found nothing; the counter says
    so."""
    config, entry = partition_entry(ops, phase_ops, seed, planted)
    got = rp.System(config).check(entry)["valid"]
    assert got == rp.reference_verdicts(entry)[0] == (planted is None)
    assert rungs == ladder
    widened = sum(w == WIDE for w, _ in ladder)
    assert telemetry.counter_value("wgl.witness.widened") == widened
    assert_traced_as_run(rungs)
