"""The plain reference against the generator's ground truth and against
hand-made histories."""

import collections
import json
import os
import random

import pytest

from benchmark.families import register as fam
from benchmark.reference import cas_register as ref

CONFIG = {"keys": 40, "ops_per_key": 60, "processes": 6, "info_rate": 0.1,
          "values": 4, "checker": "independent", "family": "register"}


@pytest.fixture(scope="module")
def pool():
    return fam.generate(CONFIG, {"pool": 2, "bad_key_share": 0.25},
                        2 ** 31 + 17)


def test_reference_matches_ground_truth(pool):
    for e in pool:
        assert fam.reference_verdicts(e) == [not k.bad for k in e.keys]


def test_search_alone_agrees_with_the_certificates(pool):
    # Where the exhaustive refutation fits the budget, it agrees.
    decided = 0
    for k in pool[0].keys:
        got = ref.search(ref.operations(k.events))
        if got != "unknown":
            assert got is (not k.bad)
            decided += 1
    assert decided >= 30


def test_a_tampered_certificate_is_refused(pool):
    k = next(k for k in pool[0].keys if not k.bad)
    ops = ref.operations(k.events)
    assert ref.check_certificate(ops, k.witness)
    assert not ref.check_certificate(ops, list(reversed(k.witness)))
    assert not ref.check_certificate(ops, k.witness[:-1])


def test_hand_made_histories():
    ok = [("invoke", "write", 1, 0), ("invoke", "read", None, 1),
          ("ok", "read", 1, 1), ("ok", "write", 1, 0)]
    assert ref.decide(ok, []) is True
    stale = [("invoke", "write", 1, 0), ("ok", "write", 1, 0),
             ("invoke", "read", None, 1), ("ok", "read", None, 1)]
    assert ref.decide(stale, []) is False
    # An :info write may have happened: a later read of it is fine ...
    info = [("invoke", "write", 2, 0), ("info", "write", 2, 0),
            ("invoke", "read", None, 1), ("ok", "read", 2, 1)]
    assert ref.decide(info, []) is True
    # ... unless :info is taken as failed: the control's broken guarantee.
    assert ref.decide(info, [], info_as_fail=True) is False
    cas = [("invoke", "cas", (None, 3), 0), ("ok", "cas", (None, 3), 0),
           ("invoke", "cas", (None, 4), 1), ("ok", "cas", (None, 4), 1)]
    assert ref.decide(cas, []) is False
    bad = ok + [("invoke", "read", None, 2), ("ok", "read", 9, 2)]
    assert ref.unsupported_read(ref.operations(bad))
    assert ref.decide(bad, []) is False


def test_same_seed_same_pool_and_every_seed_the_same_sizes():
    tr = {"pool": 2, "bad_key_share": 0.25}
    a = fam.generate(CONFIG, tr, 5)
    b = fam.generate(CONFIG, tr, 5)
    c = fam.generate(CONFIG, tr, 2 ** 31 + 5)
    assert [k.events for e in a for k in e.keys] == \
        [k.events for e in b for k in e.keys]
    assert [e.n_ops for e in a] == [e.n_ops for e in c]
    assert [sum(k.bad for k in e.keys) for e in a] == \
        [sum(k.bad for k in e.keys) for e in c] == [10, 10]
    assert a[0].keys[0].events != c[0].keys[0].events


def test_budget_gives_unknown():
    rng = random.Random(3)
    k = fam.key_history(rng, 400, 8, 0.3, 5, bad=True)
    assert ref.search(ref.operations(k.events), budget=50) == "unknown"


CLJ = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs",
                   "independent-register-200x100.json")


def test_clj_generator_keeps_the_tests_shape():
    with open(CLJ) as f:
        cfg = json.load(f)
    cfg = {**cfg, "keys": 60}
    tr = {"pool": 1, "bad_key_share": 0.0, "hidden_bad_key_share": 0.0}
    a = fam.generate(cfg, tr, 2 ** 31 + 9)[0]
    b = fam.generate(cfg, tr, 11)[0]
    # Limits: the same set for every seed, inside 0.9-1.0 x 100.
    sizes = [fam.n_invocations(k) for k in a.keys]
    assert sorted(sizes) == sorted(fam.n_invocations(k) for k in b.keys)
    assert min(sizes) >= 91 and max(sizes) <= 100
    assert sizes != [fam.n_invocations(k) for k in b.keys]
    f = collections.Counter(e[1] for k in a.keys for e in k.events
                            if e[0] == "invoke")
    n = sum(f.values())
    # Half the threads only read; the rest write:cas at 1:2.
    assert abs(f["read"] / n - 0.5) < 0.05
    assert abs(f["cas"] / f["write"] - 2) < 0.3
    for k in a.keys:
        readers = {e[3] for e in k.events if e[1] == "read"}
        writers = {e[3] for e in k.events if e[1] != "read"}
        assert readers <= set(range(cfg["readers_per_key"]))
        assert not readers & writers
        # A crashed process never runs again: it comes back as p + 10.
        crashed = [e[3] for e in k.events if e[0] == "info"]
        assert len(set(crashed)) == len(crashed)
        for p in crashed:
            last = max(i for i, e in enumerate(k.events) if e[3] == p)
            assert k.events[last][0] == "info"
        assert len({e[3] for e in k.events}) <= cfg["process_limit"]
    assert fam.reference_verdicts(a) == [True] * cfg["keys"]


def test_hidden_violation_needs_the_search():
    with open(CLJ) as f:
        cfg = json.load(f)
    kh = fam.clj_key_history(random.Random(4), 95, cfg, False)
    hid = fam.hidden_violation(kh, cfg["values"], 5, (5, 6))
    ops = ref.operations(hid.events)
    assert not ref.unsupported_read(ops)
    assert ref.decide(hid.events, hid.witness) is False
    assert ref.decide(kh.events, kh.witness) is True
