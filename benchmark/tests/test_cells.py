"""Each cell's pool, window and comparison at a tiny size on the CPU,
through the harness functions; the planted faults and the control come
out not correct."""

import dataclasses
import time

import pytest

from benchmark.harness import cell as cm, spec

TINY = {"cas100k.valid": {"ops_per_key": 1500},
        "indep200.mixed": {"keys": 50, "per_key_limit": 30}}
SEED = 2 ** 31 + 4242


def tiny(name):
    c = spec.cell(spec.load(), name)
    return dataclasses.replace(c, config={**c.config, **TINY[name]})


def run(name, system=None, traced=False, seconds=0.5):
    return cm.run(tiny(name), SEED, seconds, traced, time.monotonic(),
                  system=system)


@pytest.mark.parametrize("name", sorted(TINY))
@pytest.mark.parametrize("traced", [False, True])
def test_cell_is_correct_at_tiny_size(name, traced):
    out = run(name, traced=traced)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    names = {m["name"] for m in (tiny(name).per_layer if traced
                                 else tiny(name).end_to_end)}
    assert set(out["metrics"]) <= names
    if not traced:
        assert set(out["metrics"]) == names
        assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"


def test_pool_is_seeded_and_sized_by_the_files():
    c = tiny("indep200.mixed")
    a = c.family.generate(c.config, c.traffic, SEED)
    b = c.family.generate(c.config, c.traffic, SEED)
    assert len(a) == c.traffic["pool"]
    assert [k.events for k in a[0].keys] == [k.events for k in b[0].keys]
    n_bad = sum(round(c.config["keys"] * c.traffic[share])
                for share in ("bad_key_share", "hidden_bad_key_share"))
    assert all(sum(k.bad for k in e.keys) == n_bad for e in a)


def test_hidden_violations_pass_the_screens_and_reach_the_device():
    """The keys planted for the device search are not refuted by a
    host screen; the device BFS refutes them."""
    c = tiny("indep200.mixed")
    e = c.family.generate(c.config, c.traffic, SEED)[0]
    e.program_input = c.family.to_program(c.config, e)
    r = c.family.System(c.config).check(e)
    hidden = [k for k, kh in enumerate(e.keys)
              if kh.bad and kh.events[-1][2] != c.config["values"] + 94]
    assert hidden
    for k in hidden:
        assert r["results"][k]["valid"] is False
        assert r["results"][k]["algorithm"] != "refute-screen"


class _Planted:
    """The program, with its result altered where it is produced."""

    def __init__(self, cell, alter):
        self.inner = cell.family.System(cell.config)
        self.alter = alter
        self.verdicts = self.inner.verdicts

    def check(self, entry):
        return self.alter(self.inner.check(entry))


def flip_one(r):
    if "results" in r:
        k = next(iter(r["results"]))
        r["results"][k] = {**r["results"][k],
                           "valid": r["results"][k]["valid"] is not True}
    else:
        r["valid"] = r["valid"] is not True
    return r


def drop_half(r):
    keys = sorted(r["results"])
    r["results"] = {k: r["results"][k] for k in keys[: len(keys) // 2]}
    return r


@pytest.mark.parametrize("name,alter", [
    ("cas100k.valid", flip_one),
    ("indep200.mixed", flip_one),
    ("indep200.mixed", drop_half),
])
def test_planted_fault_is_not_correct(name, alter):
    c = tiny(name)
    out = run(name, system=_Planted(c, alter))
    assert not out["correct"]
    assert out["checks"]["verdicts_wrong"]["value"] > 0


def test_a_stream_witness_that_claims_every_key_is_not_correct(
        monkeypatch):
    """A device search that says valid without proof: the keys planted
    past the screens catch it."""
    from jepsen_tpu.ops import wgl_stream

    monkeypatch.setattr(wgl_stream, "check_wgl_witness_stream",
                        lambda packs, pm, **kw: [True] * len(packs))
    out = run("indep200.mixed")
    assert not out["correct"]
    assert out["checks"]["verdicts_wrong"]["value"] > 0


@pytest.mark.parametrize("name", sorted(TINY))
def test_control_is_not_correct(name):
    c = tiny(name)
    out = run(name, system=c.family.Control(c.config))
    assert not out["correct"]
    assert out["checks"]["verdicts_wrong"]["value"] > 0
