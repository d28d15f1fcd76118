"""Long-running cross-engine differential soak (round 4).

Reuses the CI fuzz harness (tests/test_fuzz_parity.py: five model
families, linearizable-by-construction interleavings, early injected
corruption) but runs it for a wall-clock budget with fresh seeds and a
wider size band — including sizes past the witness tier's window-roll
boundaries that the CI-sized soak never reaches.  Any CPU-vs-device
verdict disagreement is a soundness bug in one of the engines and is
printed with its reproduction seed.

Usage: python tools/fuzz_soak.py [--minutes 30] [--seed-base 0]
       [--platform cpu|default]
Prints one JSON summary line at the end; exit 1 on any mismatch.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--minutes", type=float, default=30.0)
    ap.add_argument("--seed-base", type=int, default=0)
    ap.add_argument("--platform", default="cpu",
                    choices=("cpu", "default"),
                    help='"cpu" pins the CPU backend (default); '
                         '"default" lets JAX pick the chip')
    args = ap.parse_args()

    # Append (don't setdefault): an ambient XLA_FLAGS must not
    # silently drop the 8-device split the parity suite runs under —
    # the conftest pattern.
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax

    if args.platform == "cpu":
        jax.config.update("jax_platforms", "cpu")

    from test_fuzz_parity import CONFIGS  # the CI harness, verbatim

    from jepsen_tpu.checker.wgl_cpu import check_wgl_cpu
    from jepsen_tpu.history import pack_history
    from jepsen_tpu.ops.wgl import check_wgl_device

    # CI sizes top out at 900; the soak adds sizes that cross the
    # witness window-roll and the >2000-op routing boundary for the
    # register family, and push every other family past its CI max.
    EXTRA_SIZES = {
        "cas-register": (1500, 2600),
        "multi-register": (700,),
        "mutex": (700,),
        "fifo-queue": (600,),
        "unordered-queue": (600,),
    }

    import zlib

    deadline = time.monotonic() + args.minutes * 60.0
    mismatches = []
    trials = 0
    decided: dict[str, int] = {}   # per family-size decided counts
    unknown: dict[str, int] = {}
    errors: dict[str, int] = {}
    round_i = 0
    while time.monotonic() < deadline and not mismatches:
        round_i += 1
        for name, pm_fn, hist_fn, sizes in CONFIGS:
            if time.monotonic() >= deadline or mismatches:
                break
            pm = pm_fn()
            # crc32, not hash(): string hashing is salted per process
            # and would make a reported mismatch unreproducible (the
            # CI harness's own rule).  Reproduction: same --seed-base
            # and round => same family rng => same trial sequence.
            family_seed = (args.seed_base + round_i * 1009 +
                           (zlib.crc32(name.encode()) & 0xFFFF))
            rng = random.Random(family_seed)
            for size in tuple(sizes) + EXTRA_SIZES.get(name, ()):
                for corrupt in (False, True):
                    if time.monotonic() >= deadline or mismatches:
                        break
                    key = f"{name}/{size}"
                    if errors.get(key, 0) >= 5:
                        continue  # this config is systematically sick
                    try:
                        h = hist_fn(rng, size, corrupt)
                        packed = pack_history(h, pm.encode)
                        # The soak's extra sizes get a bigger
                        # exact-oracle budget: at 20 s they mostly
                        # time out to unknown and the boundary
                        # coverage would be vacuous.
                        cpu_budget = (
                            60.0 if size in EXTRA_SIZES.get(name, ())
                            else 20.0
                        )
                        cpu = check_wgl_cpu(packed, pm,
                                            time_limit_s=cpu_budget)
                        dev = check_wgl_device(packed, pm,
                                               time_limit_s=60.0)
                    except Exception as e:  # noqa: BLE001
                        # Hours of compiles can OOM the LLVM JIT (seen
                        # at ~38 min on this box); a dying trial must
                        # not take the summary with it.
                        errors[key] = errors.get(key, 0) + 1
                        print(f"# trial error {key}: "
                              f"{type(e).__name__}: {e}",
                              file=sys.stderr, flush=True)
                        continue
                    trials += 1
                    if "unknown" in (cpu.valid, dev.valid):
                        unknown[key] = unknown.get(key, 0) + 1
                        continue
                    decided[key] = decided.get(key, 0) + 1
                    if cpu.valid is not dev.valid:
                        mismatches.append({
                            "family": name, "size": size,
                            "corrupt": corrupt, "round": round_i,
                            "family_seed": family_seed,
                            "cpu": cpu.valid, "dev": dev.valid,
                        })
                        print(f"MISMATCH: {mismatches[-1]}",
                              flush=True)
        if round_i % 5 == 0:
            print(f"# round {round_i}: {trials} trials, "
                  f"decided {sum(decided.values())}, "
                  f"unknown {sum(unknown.values())}",
                  file=sys.stderr, flush=True)
        if trials == 0 and sum(errors.values()) >= 10:
            # Nothing but errors: the environment is broken (wedged
            # backend, import failure), not merely one flaky trial —
            # don't spin the budget reporting a vacuous clean pass.
            print("# aborting: every trial errors", file=sys.stderr)
            break

    print(json.dumps({
        "trials": trials,
        "rounds": round_i,
        "decided_per_config": decided,
        "unknown_per_config": unknown,
        "errors_per_config": errors,
        "mismatches": len(mismatches),
        "minutes": round(args.minutes, 1),
    }))
    if mismatches:
        return 1
    if trials == 0:
        return 2  # vacuous run: nothing was actually compared
    return 0


if __name__ == "__main__":
    sys.exit(main())
