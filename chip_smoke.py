#!/usr/bin/env python
"""Chip smoke: drive the checker's main path once on a TPU and check it.

    python chip_smoke.py --seed 0             # one chip: phases (a)-(d)
    python chip_smoke.py --seed 0 --chips 4   # the 4-device mesh path

Phases, all in this one process (a chip belongs to one process):

  (a) device        jax version, platform, device kind and count; a
                    backend other than the TPU ends the run non-zero.
  (b) north star    a 100k-op cas-register history (16 processes, 5%
                    :info — BASELINE.json) through `core.analyze` with
                    `checker.linearizable`: valid; the same shape with
                    a planted violation: invalid.
  (c) independent   200 keys x 100 ops, ~15% planted-bad keys, through
                    `independent_checker(linearizable(...))`; every
                    per-key verdict must equal `check_wgl_cpu`, the
                    plain exact CPU search.
  (d) elle          a list-append history with a planted G2 cycle
                    through the Elle checker with its device cycle
                    screen (ops/scc.py); anomalies must equal the host
                    checker's.

`--chips 4` runs instead the path that exists only across chips: the
phase (c) histories through the shard_map batched kernel on a 4-device
mesh, one frontier-sharded search, and both again on one device.

Each phase prints one `phase <name> {json}` line: verdicts, the engine
that decided, the Pallas mode that ran, cold and warm wall seconds, and
the fallback counters.  A phase fails on a raise, a verdict unequal to
its reference, a non-zero `wgl.plan.fallback` or `wgl.degrade.*`
counter, an engine named `...-degraded` / `...-nobackend`, or a witness
that ran with Pallas off on the TPU.  The last line is the contract:
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
Times here are smoke timings, not benchmark numbers.

The phase functions import; tests/test_chip_smoke.py runs them at tiny
sizes on the CPU mesh.  This entry point accepts only a TPU backend.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def _telemetry():
    from jepsen_tpu import telemetry

    return telemetry


def _counters() -> dict:
    return dict(_telemetry().summary()["counters"])


def _fallbacks(counters: dict) -> dict:
    """The counters that must stay zero: plan-executor fallbacks and
    every degradation-ladder step."""
    return {k: v for k, v in sorted(counters.items())
            if k == "wgl.plan.fallback" or k.startswith("wgl.degrade.")}


def _pallas_modes(counters: dict) -> dict:
    pre = "wgl.witness.pallas-"
    return {k[len(pre):]: v for k, v in counters.items()
            if k.startswith(pre)}


def _engines(result) -> list:
    """Every "algorithm" named anywhere in a checker result tree."""
    out = []
    if isinstance(result, dict):
        if isinstance(result.get("algorithm"), str):
            out.append(result["algorithm"])
        for v in result.values():
            out += _engines(v)
    elif isinstance(result, list):
        for v in result:
            out += _engines(v)
    return out


def _platform() -> str:
    import jax

    return jax.devices()[0].platform


def _judge(rec: dict, counters: dict, engines: list,
           witness_ran: bool) -> dict:
    """Fills the shared failure rules into a phase record."""
    problems = rec.setdefault("problems", [])
    fb = _fallbacks(counters)
    rec["fallbacks"] = fb
    if any(v for v in fb.values()):
        problems.append(f"fallback counters non-zero: {fb}")
    bad = sorted({e for e in engines
                  if e.endswith(("-degraded", "-nobackend"))})
    if bad:
        problems.append(f"degraded engines: {bad}")
    modes = _pallas_modes(counters)
    rec["pallas"] = modes
    if _platform() == "tpu":
        if modes.get("off"):
            problems.append(f"witness ran with Pallas off on the TPU: "
                            f"{modes}")
        if witness_ran and not modes.get("on"):
            problems.append(f"no witness ran with Pallas on: {modes}")
    rec["ok"] = not problems
    return rec


def _timed(fn):
    t0 = time.monotonic()
    out = fn()
    return out, time.monotonic() - t0


# ---------------------------------------------------------------------------
# (a) device
# ---------------------------------------------------------------------------


def phase_device() -> dict:
    import jax

    from jepsen_tpu.ops import degrade

    degrade.note_backend()  # a chip held elsewhere fails here, clearly
    devs = jax.devices()
    return {
        "jax": jax.__version__,
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "ok": True,
    }


# ---------------------------------------------------------------------------
# (b) north star
# ---------------------------------------------------------------------------


def phase_north_star(seed: int, n_ops: int = 100_000, procs: int = 16,
                     info_rate: float = 0.05) -> dict:
    """The BASELINE.json history through `core.analyze`, twice (cold
    then warm), and its planted-violation twin once."""
    from jepsen_tpu import core
    from jepsen_tpu.checker.linearizable import linearizable
    from jepsen_tpu.models import cas_register
    from jepsen_tpu.utils.histgen import random_register_history

    tel = _telemetry()
    tel.reset()
    good = random_register_history(n_ops, procs=procs, info_rate=info_rate,
                                   seed=seed)
    bad = random_register_history(n_ops, procs=procs, info_rate=info_rate,
                                  seed=seed, bad=True)
    test = {"name": "chip-smoke", "model": cas_register(),
            "checker": linearizable(cas_register())}
    cold, t_cold = _timed(lambda: core.analyze(test, good))
    warm, t_warm = _timed(lambda: core.analyze(test, good))
    inv, t_bad = _timed(lambda: core.analyze(test, bad))
    rec = {
        "ops": len(good) // 2, "procs": procs, "info_rate": info_rate,
        "valid": [cold["valid"], warm["valid"]],
        "engine": cold.get("algorithm"),
        "bad_valid": inv["valid"], "bad_engine": inv.get("algorithm"),
        "cold_s": round(t_cold, 3), "warm_s": round(t_warm, 3),
        "bad_s": round(t_bad, 3),
        "problems": [],
    }
    if rec["valid"] != [True, True]:
        rec["problems"].append(f"valid history decided {rec['valid']}")
    if inv["valid"] is not False:
        rec["problems"].append(f"planted violation decided {inv['valid']}")
    return _judge(rec, _counters(), _engines([cold, warm, inv]),
                  witness_ran=True)


# ---------------------------------------------------------------------------
# (c) independent
# ---------------------------------------------------------------------------


def independent_histories(seed: int, n_keys: int = 200, key_ops: int = 100,
                          bad_share: float = 0.15) -> list:
    """Per-key histories of the jepsen.independent shape: the first
    ~bad_share of keys carry a planted violation."""
    from jepsen_tpu.utils.histgen import random_register_history

    n_bad = max(1, round(n_keys * bad_share))
    return [
        random_register_history(key_ops, procs=4, info_rate=0.05,
                                seed=seed * 100_003 + i, bad=i < n_bad)
        for i in range(n_keys)
    ]


def _reference_verdicts(hists: list) -> list:
    from jepsen_tpu.checker.wgl_cpu import check_wgl_cpu
    from jepsen_tpu.history.packed import pack_history
    from jepsen_tpu.models import cas_register

    pm = cas_register().packed()
    return [check_wgl_cpu(pack_history(h, pm.encode), pm).valid
            for h in hists]


def phase_independent(seed: int, n_keys: int = 200,
                      key_ops: int = 100) -> dict:
    from jepsen_tpu.checker.linearizable import linearizable
    from jepsen_tpu.history.core import history as make_history
    from jepsen_tpu.models import cas_register
    from jepsen_tpu.parallel.independent import (
        clear_settle_memo, independent_checker, kv,
    )

    tel = _telemetry()
    tel.reset()
    hists = independent_histories(seed, n_keys, key_ops)
    ops = []
    for i, h in enumerate(hists):
        ops += [o.replace(value=kv(f"k{i}", o.value)) for o in h]
    hist = make_history(ops)
    chk = independent_checker(linearizable(cas_register()))

    def run():
        clear_settle_memo()
        return chk.check({}, hist, {})

    cold, t_cold = _timed(run)
    warm, t_warm = _timed(run)
    ref = _reference_verdicts(hists)
    got = [cold["results"][f"k{i}"]["valid"] for i in range(n_keys)]
    got_warm = [warm["results"][f"k{i}"]["valid"] for i in range(n_keys)]
    engines = {}
    for r in cold["results"].values():
        a = r.get("algorithm", "?")
        engines[a] = engines.get(a, 0) + 1
    rec = {
        "keys": n_keys, "key_ops": key_ops,
        "invalid_keys": sum(1 for v in got if v is False),
        "reference_invalid_keys": sum(1 for v in ref if v is False),
        "engines": engines,
        "cold_s": round(t_cold, 3), "warm_s": round(t_warm, 3),
        "problems": [],
    }
    diff = [i for i in range(n_keys) if got[i] != ref[i]
            or got_warm[i] != ref[i]]
    if diff:
        rec["problems"].append(
            f"{len(diff)} keys differ from check_wgl_cpu, e.g. "
            f"{[(i, got[i], got_warm[i], ref[i]) for i in diff[:5]]}")
    return _judge(rec, _counters(), _engines([cold, warm]),
                  witness_ran=False)


# ---------------------------------------------------------------------------
# (d) elle
# ---------------------------------------------------------------------------


def g2_append_history(seed: int, n_txns: int = 200, n_keys: int = 8,
                      procs: int = 8):
    """A serial list-append history (so every anomaly is planted) with
    one write-skew pair in the middle: each of two committed txns reads
    the key the other appends to without seeing that append — two rw
    anti-dependencies, a G2-item cycle."""
    import random

    from jepsen_tpu.history.core import Op, history

    rng = random.Random(seed)
    state = {f"x{k}": [] for k in range(n_keys)}
    nxt = {k: 0 for k in state}
    ops = []

    def append(k):
        nxt[k] += 1
        return ["append", k, nxt[k]]

    def commit(p, txn):
        ops.append(Op(type="invoke", f="txn", process=p,
                      value=[[m[0], m[1], None if m[0] == "r" else m[2]]
                             for m in txn]))
        ops.append(Op(type="ok", f="txn", process=p, value=txn))

    for i in range(n_txns):
        if i == n_txns // 2:
            a, b = rng.sample(sorted(state), 2)
            ta = [["r", a, list(state[a])], append(b)]
            tb = [["r", b, list(state[b])], append(a)]
            state[b].append(ta[1][2])
            state[a].append(tb[1][2])
            commit(0, ta)
            commit(1, tb)
            continue
        txn = []
        for _ in range(rng.randint(1, 4)):
            k = rng.choice(sorted(state))
            if rng.random() < 0.5:
                txn.append(["r", k, list(state[k])])
            else:
                m = append(k)
                state[k].append(m[2])
                txn.append(m)
        commit(rng.randrange(procs), txn)
    # A final read of every key fixes each version order.
    commit(0, [["r", k, list(v)] for k, v in sorted(state.items())])
    return history(ops)


def phase_elle(seed: int, n_txns: int = 200) -> dict:
    from jepsen_tpu.checker.elle import AppendChecker

    tel = _telemetry()
    tel.reset()
    h = g2_append_history(seed, n_txns)
    dev, t_cold = _timed(lambda: AppendChecker(device="on").check({}, h, {}))
    _, t_warm = _timed(lambda: AppendChecker(device="on").check({}, h, {}))
    host = AppendChecker(device="off").check({}, h, {})
    counters = _counters()
    rec = {
        "txns": n_txns,
        "valid": dev["valid"],
        "anomaly_types": dev.get("anomaly-types"),
        "host_anomaly_types": host.get("anomaly-types"),
        "screened_graphs": counters.get("wgl.scc.screened-graphs", 0),
        "cold_s": round(t_cold, 3), "warm_s": round(t_warm, 3),
        "problems": [],
    }
    if dev.get("anomalies") != host.get("anomalies") \
            or dev["valid"] != host["valid"]:
        rec["problems"].append("device anomalies differ from the host's")
    if "G2-item" not in (dev.get("anomaly-types") or []):
        rec["problems"].append(f"planted G2 not found: "
                               f"{dev.get('anomaly-types')}")
    if not rec["screened_graphs"]:
        rec["problems"].append("the device cycle screen never ran")
    return _judge(rec, counters, [], witness_ran=False)


# ---------------------------------------------------------------------------
# --chips 4: the mesh path
# ---------------------------------------------------------------------------


def phase_mesh(seed: int, n_devices: int = 4, n_keys: int = 200,
               key_ops: int = 100) -> dict:
    """Phase (c)'s histories through the shard_map batched kernel on an
    n-device mesh and on one device, plus one frontier-sharded search
    against its one-device run.  Per-key work must land on every
    device: each shard of the sharded kernel's explored-count output
    lives on its own device and explored configurations."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from jepsen_tpu.history.packed import pack_history
    from jepsen_tpu.models import cas_register
    from jepsen_tpu.ops import wgl_batched
    from jepsen_tpu.ops.wgl import check_wgl_device
    from jepsen_tpu.parallel.mesh import default_mesh

    tel = _telemetry()
    tel.reset()
    rec: dict = {"devices": n_devices, "keys": n_keys, "problems": []}
    if len(jax.devices()) < n_devices:
        rec["problems"].append(
            f"need {n_devices} devices, found {len(jax.devices())}")
        return _judge(rec, _counters(), [], witness_ran=False)
    pm = cas_register().packed()
    hists = independent_histories(seed, n_keys, key_ops)
    packs = [pack_history(h, pm.encode) for h in hists]
    ref = _reference_verdicts(hists)
    mesh_n = default_mesh(n_devices)
    mesh_1 = default_mesh(1)

    sharded, t_cold = _timed(
        lambda: wgl_batched.check_wgl_batched(packs, pm, mesh=mesh_n))
    sharded, t_warm = _timed(
        lambda: wgl_batched.check_wgl_batched(packs, pm, mesh=mesh_n))
    single, t_one = _timed(
        lambda: wgl_batched.check_wgl_batched(packs, pm, mesh=mesh_1))
    rec.update({
        "batched_cold_s": round(t_cold, 3),
        "batched_warm_s": round(t_warm, 3),
        "one_device_s": round(t_one, 3),
        "sharded_invalid": sum(1 for v in sharded.valid if v is False),
        "sharded_unknown": sum(1 for v in sharded.valid if v == "unknown"),
    })
    if sharded.valid != single.valid:
        diff = [i for i in range(n_keys)
                if sharded.valid[i] != single.valid[i]]
        rec["problems"].append(f"sharded != one-device on keys {diff[:8]}")
    wrong = [i for i, v in enumerate(sharded.valid)
             if v != "unknown" and v != ref[i]]
    if wrong:
        rec["problems"].append(f"sharded verdicts differ from "
                               f"check_wgl_cpu on keys {wrong[:8]}")

    # Where the work ran: one launch of the same sharded kernel.
    bp = wgl_batched.pack_batch(packs, pad_keys_to=n_keys)
    B = wgl_batched._bucket(256, lo=32)
    fn = wgl_batched._get_kernel(B, bp.N, pm.state_width, 4 * B,
                                 pm.jax_step, mesh_n,
                                 packed=wgl_batched.packed_enabled(None))
    init = np.asarray(pm.init_state, dtype=np.int32)
    out = fn(*(jnp.asarray(a) for a in (bp.ret, bp.inv, bp.f, bp.a0,
                                         bp.a1, bp.okv)),
             jnp.asarray(init), jnp.asarray(bp.n_ops))
    expl = out[3]
    per_dev = {str(s.device.id): int(np.asarray(s.data).sum())
               for s in expl.addressable_shards}
    rec["explored_per_device"] = per_dev
    if len(per_dev) != n_devices or not all(per_dev.values()):
        rec["problems"].append(f"per-key work not on every device: "
                               f"{per_dev}")

    # One frontier-sharded search: an invalid key forces the BFS tier
    # (the witness only ever proves valid).
    bad_i = next(i for i, v in enumerate(ref) if v is False)
    beam_mesh = default_mesh(n_devices, axis="beam")
    r_n, t_fn = _timed(lambda: check_wgl_device(
        packs[bad_i], pm, mesh=beam_mesh, time_limit_s=300))
    r_1, t_f1 = _timed(lambda: check_wgl_device(
        packs[bad_i], pm, mesh=default_mesh(1, axis="beam"),
        time_limit_s=300))
    rec.update({"frontier_sharded_valid": r_n.valid,
                "frontier_one_device_valid": r_1.valid,
                "frontier_sharded_s": round(t_fn, 3),
                "frontier_one_device_s": round(t_f1, 3)})
    if not (r_n.valid is r_1.valid is False):
        rec["problems"].append(
            f"frontier search: sharded {r_n.valid}, one device "
            f"{r_1.valid}, reference False")
    return _judge(rec, _counters(), [], witness_ran=False)


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh path and its one-device "
                         "comparison")
    args = ap.parse_args(argv)

    from jepsen_tpu import compile_cache

    print(f"compile cache: {compile_cache.place()}", flush=True)
    _telemetry().enable(True)

    dev = phase_device()
    print("phase device " + json.dumps(dev), flush=True)
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev['platform']}",
              file=sys.stderr)
        return 2
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{dev['count']} devices", file=sys.stderr)
        return 2

    if args.chips == 4:
        phases = [("mesh", lambda: phase_mesh(args.seed, 4))]
    else:
        phases = [
            ("north-star", lambda: phase_north_star(args.seed)),
            ("independent", lambda: phase_independent(args.seed)),
            ("elle", lambda: phase_elle(args.seed)),
        ]
    ok = True
    for name, fn in phases:
        try:
            rec = fn()
        except Exception as e:  # noqa: BLE001 — a raise fails the phase
            import traceback

            traceback.print_exc()
            rec = {"ok": False, "problems": [f"{type(e).__name__}: {e}"]}
        print(f"phase {name} " + json.dumps(rec, default=str), flush=True)
        ok = ok and rec["ok"]
    if not ok:
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
