#!/usr/bin/env python
"""CI smoke for the checking-plan subsystem (tier1.yml step).

Four phases over one fixed mixed 60-key register history (48 valid
keys, 12 that defeat the checker):

  1. COLD  — a fresh process with `JEPSEN_PLAN=1` and
     `JEPSEN_PLAN_CACHE` pointing at an empty directory checks the
     history; must journal plan-memo entries and populate the XLA
     compile cache.
  2. WARM  — a second fresh process over the same cache directory
     re-checks the identical history; must HIT the persistent plan
     memo, add no new XLA cache files (every kernel compile is
     served from disk), produce byte-identical per-key verdicts, and
     not be slower than the cold run.
  3. PARITY — a fresh process with `JEPSEN_PLAN=0` (the hand-wired
     legacy ladder) must produce the same per-key (valid, algorithm)
     pairs as the cold plan run.
  4. DAEMON — a checkerd daemon started with `--plan-cache`, fed one
     remote run, then killed and RESTARTED over the same directory:
     the resubmitted history must hit the journaled plan memo
     (stats()["plan"]["cache"]["memo"]["hits"] > 0).

Exit 0 + "PASS" on success, exit 1 with a reason otherwise.  CPU-only:
the workflow runs it under JAX_PLATFORMS=cpu.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

N_KEYS = 60
BAD_EVERY = 5  # keys 4, 9, 14, ... read a never-written value
PAIRS = 4


def build_history():
    from jepsen_tpu.history.core import History
    from jepsen_tpu.parallel.independent import KV

    ops = []

    def add(process, f, key, value, ok_value=None):
        i = len(ops)
        ops.append({"index": i, "type": "invoke", "process": process,
                    "f": f, "value": KV(key, None if f == "read" else value),
                    "time": i})
        ops.append({"index": i + 1, "type": "ok", "process": process,
                    "f": f,
                    "value": KV(key, value if ok_value is None else ok_value),
                    "time": i + 1})

    for k in range(N_KEYS):
        key = f"k{k:03d}"
        bad = (k % BAD_EVERY) == BAD_EVERY - 1
        for v in range(PAIRS):
            add(k % 8, "write", key, v)
            # A bad key's last read observes a value never written.
            if bad and v == PAIRS - 1:
                add(k % 8, "read", key, None, ok_value=99)
            else:
                add(k % 8, "read", key, v)
    return History(ops)


def worker(out_path: str) -> int:
    """One fresh-process check of the fixed history; plan/cache config
    comes from the environment (JEPSEN_PLAN / JEPSEN_PLAN_CACHE)."""
    from jepsen_tpu import telemetry
    from jepsen_tpu.checker.linearizable import Linearizable
    from jepsen_tpu.models.registers import Register
    from jepsen_tpu.parallel.independent import IndependentChecker

    telemetry.enable(True)
    telemetry.reset()
    h = build_history()
    t0 = time.monotonic()
    res = IndependentChecker(Linearizable(Register())).check(
        {"name": "plan-smoke"}, h, {"history-key": None})
    wall_s = time.monotonic() - t0
    counters = telemetry.summary()["counters"]
    from jepsen_tpu.plan import cache as plan_cache

    report = {
        "valid": res.get("valid"),
        "results": {
            str(k): {"valid": r.get("valid"),
                     "algorithm": r.get("algorithm")}
            for k, r in (res.get("results") or {}).items()
        },
        "wall_s": round(wall_s, 3),
        "counters": {k: v for k, v in sorted(counters.items())
                     if k.startswith(("wgl.plan.", "wgl.settle."))},
        "cache": plan_cache.stats(),
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    return 0


def run_worker(tag: str, tmp: str, *, plan: str,
               cache: str | None) -> dict:
    out = os.path.join(tmp, f"{tag}.json")
    env = dict(os.environ)
    env["JEPSEN_PLAN"] = plan
    env.pop("JEPSEN_PLAN_CACHE", None)
    # The compile cache lives where JAX_COMPILATION_CACHE_DIR says; a
    # fresh one per smoke so the cold run starts empty.
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(tmp, "xla")
    if cache is not None:
        env["JEPSEN_PLAN_CACHE"] = cache
    rc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--worker", out],
        env=env, timeout=600,
    ).returncode
    if rc != 0:
        fail(f"{tag} worker exited rc={rc}")
    with open(out) as f:
        return json.load(f)


def fail(msg: str) -> None:
    print(f"FAIL: {msg}")
    sys.exit(1)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def check_verdicts(tag: str, rep: dict) -> None:
    for k, r in rep["results"].items():
        bad = (int(k[1:]) % BAD_EVERY) == BAD_EVERY - 1
        if r["valid"] is not (not bad):
            fail(f"{tag}: key {k} valid={r['valid']}, "
                 f"expected {not bad}")
    if rep["valid"] is not False:
        fail(f"{tag}: top-level valid={rep['valid']}, expected False")


def daemon_phase(tmp: str) -> dict:
    """Start checkerd --plan-cache, run once, restart, rerun: the
    second daemon must warm-start from the journaled plan memo."""
    from jepsen_tpu.checker.linearizable import Linearizable
    from jepsen_tpu.checkerd.client import CheckerdClient, RemoteChecker
    from jepsen_tpu.models.registers import Register
    from jepsen_tpu.parallel.independent import IndependentChecker

    cache = os.path.join(tmp, "daemon-cache")
    h = build_history()
    stats = {}
    for round_no in (1, 2):
        port = free_port()
        addr = f"127.0.0.1:{port}"
        daemon = subprocess.Popen(
            [sys.executable, "-m", "jepsen_tpu.checkerd",
             "--host", "127.0.0.1", "--port", str(port),
             "--batch-window", "0.2", "--platform", "cpu",
             "--plan-cache", cache],
        )
        try:
            deadline = time.monotonic() + 60
            while True:
                try:
                    with socket.create_connection(("127.0.0.1", port),
                                                  timeout=1):
                        break
                except OSError:
                    if daemon.poll() is not None:
                        fail(f"daemon round {round_no} exited early "
                             f"rc={daemon.returncode}")
                    if time.monotonic() > deadline:
                        fail(f"daemon round {round_no} never listened")
                    time.sleep(0.2)
            rc = RemoteChecker(
                IndependentChecker(Linearizable(Register())),
                addr, run_id=f"plan-smoke-{round_no}", fallback=False)
            res = rc.check({"name": "plan-smoke"}, h, {})
            if "fallback" in res.get("checkerd", {}):
                fail(f"daemon round {round_no} fell back in-process: "
                     f"{res['checkerd']}")
            with CheckerdClient(addr) as c:
                stats = c.stats()
        finally:
            daemon.terminate()
            try:
                daemon.wait(timeout=10)
            except subprocess.TimeoutExpired:
                daemon.kill()
    return stats


def main() -> None:
    if len(sys.argv) > 2 and sys.argv[1] == "--worker":
        sys.exit(worker(sys.argv[2]))

    tmp = tempfile.mkdtemp(prefix="plan-smoke-")
    cache = os.path.join(tmp, "cache")

    cold = run_worker("cold", tmp, plan="1", cache=cache)
    check_verdicts("cold", cold)
    memo = (cold["cache"].get("memo") or {})
    if not memo.get("puts"):
        fail(f"cold run journaled no plan-memo entries: {memo}")
    xla_after_cold = cold["cache"].get("xla_files") or 0
    if not xla_after_cold:
        fail("cold run populated no XLA compile-cache files")

    warm = run_worker("warm", tmp, plan="1", cache=cache)
    check_verdicts("warm", warm)
    wmemo = (warm["cache"].get("memo") or {})
    if not wmemo.get("hits"):
        fail(f"warm run hit no plan-memo entries: {wmemo}")
    xla_after_warm = warm["cache"].get("xla_files") or 0
    if xla_after_warm > xla_after_cold:
        fail(f"warm run compiled {xla_after_warm - xla_after_cold} "
             f"new kernels ({xla_after_cold} -> {xla_after_warm})")
    if warm["results"] != cold["results"]:
        fail("warm/cold per-key verdicts differ")
    # "Not slower": generous jitter allowance — CI boxes are loud, but
    # a warm run paying full recompilation would be MUCH slower.
    if warm["wall_s"] > cold["wall_s"] * 1.25 + 1.0:
        fail(f"warm run slower than cold: {warm['wall_s']}s vs "
             f"{cold['wall_s']}s")

    legacy = run_worker("legacy", tmp, plan="0", cache=None)
    check_verdicts("legacy", legacy)
    mismatch = {
        k for k in cold["results"]
        if cold["results"][k] != legacy["results"].get(k)
    }
    if mismatch:
        examples = {k: (cold["results"][k], legacy["results"].get(k))
                    for k in sorted(mismatch)[:4]}
        fail(f"plan/legacy per-pass parity broke on "
             f"{len(mismatch)} keys: {examples}")
    if not any(k.startswith("wgl.plan.") for k in cold["counters"]):
        fail(f"cold run emitted no wgl.plan.* counters: "
             f"{cold['counters']}")
    if any(k.startswith("wgl.plan.") for k in legacy["counters"]):
        fail(f"legacy run emitted plan counters: {legacy['counters']}")

    stats = daemon_phase(tmp)
    plan_stats = stats.get("plan") or {}
    dmemo = ((plan_stats.get("cache") or {}).get("memo")) or {}
    if not dmemo.get("hits"):
        fail(f"restarted daemon warm-started nothing: {dmemo}")

    print(f"PASS: cold {cold['wall_s']}s -> warm {warm['wall_s']}s, "
          f"memo {memo.get('puts')} stored / {wmemo.get('hits')} hit, "
          f"xla files {xla_after_cold} (no new on warm), "
          f"legacy parity on {len(cold['results'])} keys, "
          f"daemon warm-start hits={dmemo.get('hits')}")


if __name__ == "__main__":
    main()
