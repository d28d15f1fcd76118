#!/usr/bin/env python
"""A/B the witness engine's transfer modes on the current platform.

transfer="full" ships pre-gathered (NB,6,K)+(NB,5,W) block tables per
chunk call (~74 KB/block); "indices" uploads the per-row tables once
and ships only row-index arrays (~22 KB/block), rebuilding tables on
device; "device" (round 5) plans the blocks on device too — ~640 B
per chunk and no host-side per-block numpy at all.  CPU measures
"full" fastest (the device IS the host's cores, so host-built tables
win); on the TPU, "device" removes the full mode's ~5 MB/100k-op
per-check upload and its serialized host numpy (chip time: not
measured).

Usage: python tools/transfer_ab.py [--ops 100000] [--reps 2]
       [--platform default|cpu]
Prints one JSON line per mode.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ops", type=int, default=100_000)
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--platform", default="default")
    args = ap.parse_args()

    import jax

    if args.platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform

    from jepsen_tpu.history.packed import pack_history
    from jepsen_tpu.models import cas_register
    from jepsen_tpu.ops import wgl_witness as ww
    from jepsen_tpu.utils.histgen import random_register_history

    pm = cas_register().packed()
    h = random_register_history(args.ops, procs=16, info_rate=0.05,
                                seed=45100)
    packed = pack_history(h, pm.encode)
    width = ww.plan_width(packed)

    for mode in ("full", "indices", "device"):
        ww.check_wgl_witness(packed, pm, transfer=mode,
                             width_hint=width)  # warm
        times = []
        for _ in range(args.reps):
            t0 = time.monotonic()
            r = ww.check_wgl_witness(packed, pm, transfer=mode,
                                     width_hint=width)
            dt = time.monotonic() - t0
            assert r is not None and r.valid is True
            times.append(dt)
        from jepsen_tpu.utils import summarize_times

        s = summarize_times(times)
        print(json.dumps({
            "mode": mode, "ops": args.ops, **s,
            "ops_per_s": round(args.ops / s["median_s"]),
            "platform": platform,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
