"""The partition family (`families/register_partition.py`) and its cell,
`cas100k.partition`: the pool follows the files, the histories have the
shape the configuration states, a tiny cell is correct and reads the
witness ladder's metrics, and the control is not correct."""

import dataclasses
import time

import pytest

from benchmark.harness import cell as cm, spec

NAME = "cas100k.partition"
SEED = 2 ** 31 + 4242
#: About 10k ops a history, in ten phases as the full size has twenty.
TINY = {"ops_per_key": 10_000, "phase_ops": 1000}


def cell(**sizes):
    c = spec.cell(spec.load(), NAME)
    return dataclasses.replace(c, config={**c.config, **sizes})


@pytest.fixture
def narrow(monkeypatch):
    """The narrow rung's window below a tiny history's :info count, so
    that the ladder runs as it does at full size."""
    from jepsen_tpu.ops import wgl_witness

    monkeypatch.setattr(wgl_witness, "NARROW_INFO_WINDOW", 64)


def test_pool_is_seeded_and_sized_by_the_files():
    c = cell(**TINY)
    a = c.family.generate(c.config, c.traffic, SEED)
    b = c.family.generate(c.config, {**c.traffic, "bad_key_share": 1.0},
                          SEED)
    assert len(a) == c.traffic["pool"] == len(b)
    assert [k.events for k in a[0].keys] == \
        [k.events for k in c.family.generate(c.config, c.traffic,
                                             SEED)[0].keys]
    assert all(e.n_ops == c.config["ops_per_key"] for e in a)
    assert not any(k.bad for e in a for k in e.keys)
    assert all(k.bad and k.events[-1][2] == c.config["values"] + 94
               for e in b for k in e.keys)


def phases(c, events):
    """Per phase, the nodes whose threads completed :info or read
    :fail; and per process, its ops' completion types in order."""
    threads, nodes = c.config["threads"], c.config["nodes"]
    hit: dict = {}
    by_proc: dict = {}
    open_: dict = {}
    started = 0
    for typ, f, _, p in events:
        if typ == "invoke":
            open_[p] = (started // c.config["phase_ops"], f)
            started += 1
            continue
        phase, f0 = open_.pop(p)
        by_proc.setdefault(p, []).append(typ)
        if typ == "info" or (typ == "fail" and f0 == "read"):
            hit.setdefault(phase, set()).add(p % threads % nodes)
    return hit, by_proc


def test_full_size_histories_keep_to_the_configuration():
    """At the full size: 12-15k :info ops, all writes or cas, 17-23% of
    them; :info and failed reads only in partitioned phases and only
    from at most `minority_nodes` nodes; a process ends at its :info, so
    there is a process id for each."""
    c = cell()
    n_phases = -(-c.config["ops_per_key"] // c.config["phase_ops"])
    minority = c.config["minority_nodes"]
    for e in c.family.generate(c.config, c.traffic, SEED):
        events = e.keys[0].events
        info = [ev for ev in events if ev[0] == "info"]
        writes = sum(ev[0] == "invoke" and ev[1] != "read" for ev in events)
        assert 12_000 <= len(info) <= 15_000
        assert all(ev[1] in ("write", "cas") for ev in info)
        assert 0.17 <= len(info) / writes <= 0.23
        hit, by_proc = phases(c, events)
        assert set(hit) <= set(range(1, n_phases, 2))
        assert all(len(n) <= minority for n in hit.values())
        assert sum(ev[0] == "fail" and ev[1] == "read" for ev in events) \
            > 5_000
        assert all("info" not in ts[:-1] for ts in by_proc.values())
        assert len(by_proc) >= len(info) > 10_000


@pytest.mark.parametrize("traced", [False, True])
def test_tiny_cell_is_correct(traced, narrow):
    c = cell(**TINY)
    out = cm.run(c, SEED, 0.5, traced, time.monotonic())
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    names = {m["name"] for m in (c.per_layer if traced else c.end_to_end)}
    assert set(out["metrics"]) == names
    if traced:
        m = {k: v["value"] for k, v in out["metrics"].items()}
        assert m["witness_widened"] == 1.0
        assert m["witness_wide_s"] > 0 and m["witness_narrow_s"] > 0


def test_control_is_not_correct(narrow):
    c = cell(**TINY)
    out = cm.run(c, SEED, 0.5, False, time.monotonic(),
                 system=c.family.Control(c.config))
    assert not out["correct"]
    assert out["checks"]["verdicts_wrong"]["value"] > 0
