"""BENCHMARK.json keeps to the benchmark's contract, and every name in it
leads to a file of its own."""

import json
import os
import re

import pytest

from benchmark.harness import spec

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
TEXT = re.compile(r"[^\n\t]{1,200}")


@pytest.fixture(scope="module")
def bench():
    return spec.load()


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark"]
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench)) <= 64 * 1024


def test_configs(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.fullmatch(c["name"]) and TEXT.fullmatch(c["source"])
        assert TEXT.fullmatch(c["why"])
        assert c["file"].startswith("benchmark/")
        with open(os.path.join(spec.ROOT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]
    assert len({c["source"] for c in bench["configs"]}) == \
        len(bench["configs"])


def test_workloads_find_their_files(bench):
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.fullmatch(w["name"]) and w["chips"] in (1, 4)
        assert TEXT.fullmatch(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        c = spec.cell(bench, w["name"])
        assert c.end_to_end and c.per_layer
        assert "setup_s" in {m["name"] for m in c.end_to_end}


def test_metrics_have_readers(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
        assert UNIT.fullmatch(m["unit"])
        assert hasattr(spec.load_module("e2e", m["name"]), "read")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and TEXT.fullmatch(m["layer"])
        assert set(m.get("workloads", cells)) <= cells
        assert UNIT.fullmatch(m["unit"]) and m["better"] in (
            "lower", "higher")
        assert hasattr(spec.load_module("layers", m["name"]), "read")
