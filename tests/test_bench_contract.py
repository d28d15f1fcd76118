"""bench.py driver contract: exactly one JSON line on stdout, with the
required fields, on the CPU smoke path.  The driver records this line
as the round's metric (BENCH_r{N}.json), so the contract is CI-guarded
here; the TPU path is the same code under a different backend."""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "bench.py")


@pytest.mark.slow
def test_bench_emits_one_json_line_cpu():
    env = dict(
        os.environ,
        JEPSEN_BENCH_PLATFORM="cpu",
        JEPSEN_BENCH_OPS="3000",
        JEPSEN_BENCH_PROCS="8",
        JEPSEN_BENCH_TIME_LIMIT="120",
        # CI-sized scale point: the full default (20M rows) costs
        # minutes per suite run; 1M still exercises the whole
        # second-metric path (generate -> check -> merge).
        JEPSEN_BENCH_SCALE_OPS="1000000",
        JAX_PLATFORMS="cpu",
    )
    proc = subprocess.run(
        [sys.executable, BENCH],
        env=env, capture_output=True, timeout=420,
    )
    out = proc.stdout.decode()
    lines = [l for l in out.splitlines() if l.strip()]
    assert proc.returncode == 0, (out, proc.stderr.decode()[-2000:])
    assert len(lines) == 1, lines
    rec = json.loads(lines[0])
    assert rec["metric"] == "wgl_linearizability_throughput"
    assert rec["unit"] == "ops/s"
    assert rec["value"] > 0
    assert rec["vs_baseline"] > 0
    assert rec["platform"] == "cpu"
    assert "error" not in rec
    # Telemetry phases breakdown rides the same line and must not
    # break its single-line parseability (it just did: json.loads
    # above) or depend on JEPSEN_TELEMETRY being set.
    phases = rec["phases"]
    assert set(phases) >= {"generate", "pack", "warmup", "check"}
    assert all(isinstance(v, (int, float)) and v >= 0
               for v in phases.values())
    assert phases["check"] > 0
    # Second headline metric (VERDICT r4 #4) rides the SAME line.
    scale = rec["scale"]
    assert scale["metric"] == "scale_ops_to_verdict"
    assert scale["valid"] is True
    assert scale["ops"] >= 900_000
    assert scale["max_ops_at_300s"] > scale["ops"]
