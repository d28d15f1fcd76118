"""Independent-checking capture for the chip battery (and by hand).

Measures the 200-key x 100-op jepsen.independent shape — the workload
tests/test_whole_stack_perf.py floors on the CPU mesh — on whatever
backend is available, in two variants:

  * **all-valid** — every key linearizable: the key-concatenated
    stream witness (ops/wgl_stream.py) should decide all keys in one
    device pass.
  * **mixed** — ~15% of keys carry a planted violation: the cohort
    settling ladder (parallel/independent.py: stream -> memo ->
    refutation screens -> batched BFS -> parallel CPU settle) does the
    work; the settle memo is cleared before every rep so each rep
    prices the cold ladder.

Each variant runs >= --reps measured reps (plus one compile warm-up)
and prints ONE JSON line with median + spread (utils.summarize_times)
and the backend platform, so a reader can tell a chip capture from a
CPU one.

Usage:
  python tools/independent_bench.py [--keys 200] [--key-ops 100]
      [--reps 3] [--platform default|cpu]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _build(n_keys: int, key_ops: int, n_bad: int):
    from jepsen_tpu.history.core import history as make_history
    from jepsen_tpu.parallel.independent import kv
    from jepsen_tpu.utils.histgen import random_register_history

    ops = []
    for i in range(n_keys):
        h = random_register_history(key_ops, procs=4, info_rate=0.05,
                                    seed=i, bad=(i < n_bad))
        ops += [o.replace(value=kv(f"k{i}", o.value)) for o in h]
    return make_history(ops)


def measure(name: str, hist, n_bad: int, reps: int, platform: str,
            time_limit_s: float) -> dict:
    from jepsen_tpu.checker.linearizable import Linearizable
    from jepsen_tpu.models import cas_register
    from jepsen_tpu.parallel.independent import (
        IndependentChecker, clear_settle_memo,
    )
    from jepsen_tpu.parallel.mesh import default_mesh
    from jepsen_tpu.utils import summarize_times

    from jepsen_tpu import telemetry
    from jepsen_tpu.telemetry import flight, profile

    chk = IndependentChecker(
        Linearizable(cas_register(), time_limit_s=time_limit_s)
    )
    test = {"mesh": default_mesh()}
    # With JEPSEN_TELEMETRY=1 the observatory rides along: the record
    # gains profile_records + flight status so the BENCH trajectory
    # prices the instrumentation's own overhead (<2% target on the
    # mixed shape).
    profile_dir = None
    if telemetry.enabled():
        import tempfile

        profile_dir = tempfile.mkdtemp(prefix=f"bench-profiles-{name}-")
        profile.set_store(profile_dir)
        flight.reset()
    times = []
    for rep in range(reps + 1):  # rep 0 = compile warm-up, not counted
        clear_settle_memo()
        t0 = time.monotonic()
        res = chk.check(test, hist, {})
        dt = time.monotonic() - t0
        expect_valid = n_bad == 0
        if (res["valid"] is True) is not expect_valid or \
                res.get("failure-count", 0) != n_bad:
            return {
                "metric": f"independent_{name}",
                "platform": platform,
                "error": (
                    f"expected {'valid' if expect_valid else 'invalid'}"
                    f" with {n_bad} failures, got valid={res['valid']} "
                    f"failures={res.get('failure-count')}"
                ),
            }
        if rep > 0:
            times.append(dt)
    stats = summarize_times(times)
    rec = {
        "metric": f"independent_{name}",
        "platform": platform,
        "ops_per_s": round((len(hist) / 2) / stats["median_s"], 1),
        **stats,
    }
    if profile_dir is not None:
        rec["profile_records"] = profile.count_records()
        rec["flight"] = flight.status()
        profile.set_store(None)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys", type=int, default=200)
    ap.add_argument("--key-ops", type=int, default=100)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--time-limit", type=float, default=300.0)
    ap.add_argument("--platform", default="default",
                    choices=["default", "cpu"])
    args = ap.parse_args()

    if args.platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax

    platform = jax.devices()[0].platform

    rc = 0
    n_bad = max(1, round(args.keys * 0.15))
    for name, bad in (("stream_all_valid", 0), ("mixed", n_bad)):
        hist = _build(args.keys, args.key_ops, bad)
        rec = measure(name, hist, bad, args.reps, platform,
                      args.time_limit)
        rec.update(keys=args.keys, key_ops=args.key_ops, bad_keys=bad)
        print(json.dumps(rec), flush=True)
        if "error" in rec:
            rc = 1
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
