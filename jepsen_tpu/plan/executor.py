"""The plan executor: one engine that runs any checking plan.

`execute(plan, ctx, seeds)` is a single forward sweep over the plan's
nodes (the IR guarantees edges point forward): each node's pass family
runner decides keys, routes the rest along the node's typed edges, and
the sweep carries work queues node to node.  The contiguous tail of
`group=True` nodes is the **digest-dedup scope** — the settle-memo
mechanic of `IndependentChecker._settle_cohort` hoisted into the
executor: on entry, keys collapse to one representative per packed
digest (memo hits — in-memory settle memo first, then the persistent
plan memo — skip the scope entirely); on exit, each representative's
verdict fans out to its group, sanitized of positional certificates.

The family runners call the *same* engine helpers the legacy ladder
calls (`check_wgl_witness_stream`, `check_refute`, `check_wgl_batched`,
the `"settle"`-algorithm Linearizable, `_memo_get`/`_memo_put`), emit
the same `wgl.settle.*` counters, and wrap the group scope in the same
`profile.capture("settle")` record — so `JEPSEN_PLAN=1` and `=0`
produce identical verdicts, counters, and training records by
construction.  Plan-level telemetry lands under `wgl.plan.*`.
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import time
from collections import OrderedDict
from typing import Any, Callable, Optional

from .. import telemetry
from ..telemetry import profile
from . import cache as plan_cache
from .ir import PassFamily, PassNode, Plan, family, register_family

log = logging.getLogger(__name__)


@dataclasses.dataclass
class ExecContext:
    """Everything a runner needs: the cohort's data, the checker
    template whose knobs seed the engines, shared budget state, and
    per-key scratch notes (device verdicts, screen outcomes)."""

    test: dict
    subs: dict
    packs: dict
    model: Any
    pm: Any
    lin: Any
    opts: dict
    bound: Optional[int] = None
    mesh: Any = None
    checker: Any = None
    #: "cohort" (IndependentChecker), "packs" (checkerd wire-packed),
    #: or "single" (one Linearizable history).
    mode: str = "cohort"
    #: packs mode: absolute monotonic deadline (checkerd budget).
    deadline: Optional[float] = None
    #: Plan-identity facts for the persistent memo key: model name /
    #: init state / algorithm / budgets.  Changing any of them misses.
    identity: dict = dataclasses.field(default_factory=dict)
    notes: dict = dataclasses.field(default_factory=dict)
    counts: dict = dataclasses.field(default_factory=dict)
    _digests: dict = dataclasses.field(default_factory=dict)
    _t0: Optional[float] = None

    # -- shared tier budget (the legacy t_tiers clock) ----------------------

    def start_clock(self) -> None:
        if self._t0 is None:
            self._t0 = time.monotonic()

    def budget_left(self) -> Optional[float]:
        if self.mode == "packs":
            if self.deadline is None:
                return None
            return max(1.0, self.deadline - time.monotonic())
        if self.lin.time_limit_s is None:
            return None
        self.start_clock()
        return max(
            1.0, self.lin.time_limit_s - (time.monotonic() - self._t0)
        )

    # -- per-key helpers ----------------------------------------------------

    def digest(self, k: Any) -> str:
        d = self._digests.get(k)
        if d is None:
            from ..parallel.independent import _settle_digest

            d = self._digests[k] = _settle_digest(self.packs[k], self.pm)
        return d

    def pmemo_key(self, k: Any) -> str:
        return plan_cache.memo_key(self.digest(k), self.identity)

    def note(self, k: Any) -> dict:
        n = self.notes.get(k)
        if n is None:
            n = self.notes[k] = {}
        return n

    def count(self, name: str, n: int = 1) -> None:
        self.counts[name] = self.counts.get(name, 0) + n


# ---------------------------------------------------------------------------
# Family runners — each reuses the exact legacy engine call.
# ---------------------------------------------------------------------------


def _run_host_fallback(ctx: ExecContext, node: PassNode, keys: list):
    """Keys with no packed form: the single-key checker under
    bounded_pmap, exactly the legacy unpackable path."""
    from ..checker.core import check_safe
    from ..utils import bounded_pmap

    lin = ctx.lin
    rs = bounded_pmap(
        lambda k: check_safe(
            lin, ctx.test, ctx.subs[k], {**ctx.opts, "history_key": k}
        ),
        keys,
        bound=ctx.bound,
    )
    return dict(zip(keys, rs)), {}


def _run_online(ctx: ExecContext, node: PassNode, keys: list):
    """Digest-gated consumption of a streaming session's online proofs
    (can-prove-valid: a consumed verdict was proven while the run was
    still generating)."""
    from ..parallel.independent import _online_digest

    sess = (ctx.test or {}).get("streaming-session")
    decided: dict = {}
    if sess is not None:
        for k in keys:
            d = _online_digest(sess, ctx.pm, ctx.subs[k])
            r = sess.consume(k, d) if d is not None else None
            if r is not None:
                decided[k] = r
    if decided and telemetry.enabled():
        telemetry.count("wgl.settle.online-proven", len(decided))
    rest = [k for k in keys if k not in decided]
    return decided, ({"unknown": rest} if rest else {})


def _run_pmemo(ctx: ExecContext, node: PassNode, keys: list):
    """Persistent plan-memo lookup (cache.py): a restarted process
    re-checking byte-identical work replays the journaled verdict."""
    pmemo = plan_cache.active_memo()
    if pmemo is None or not keys:
        return {}, ({"unknown": list(keys)} if keys else {})
    decided, rest = {}, []
    for k in keys:
        hit = pmemo.get(ctx.pmemo_key(k))
        if hit is not None:
            hit["memo-hit"] = True
            decided[k] = hit
        else:
            rest.append(k)
    return decided, ({"unknown": rest} if rest else {})


def _run_length_router(ctx: ExecContext, node: PassNode, keys: list):
    """Routes long keys (batched-kernel compile/pad cost scales with
    the LONGEST key) to the per-key device ladder; decides nothing."""
    thr = node.knobs.get("threshold", 2000)
    long_keys = [k for k in keys if ctx.packs[k].n > thr]
    short = [k for k in keys if ctx.packs[k].n <= thr]
    routed: dict = {}
    if long_keys:
        routed["long"] = long_keys
    if short:
        routed["unknown"] = short
    return {}, routed


def _run_single_device(ctx: ExecContext, node: PassNode, keys: list):
    """Per-key witness-first device ladder (check_wgl_device) for keys
    too long for the batched kernel."""
    from ..checker.core import check_safe
    from ..checker.linearizable import Linearizable
    from ..utils import bounded_pmap

    lin = ctx.lin
    long_chk = Linearizable(
        ctx.model, "wgl-tpu",
        beam=lin.beam, max_beam=lin.max_beam,
        time_limit_s=lin.time_limit_s,
        max_configs=lin.max_configs,
    )
    rs = bounded_pmap(
        lambda k: check_safe(
            long_chk, ctx.test, ctx.subs[k], {**ctx.opts, "history_key": k}
        ),
        keys,
        bound=ctx.bound,
    )
    return dict(zip(keys, rs)), {}


def _run_stream(ctx: ExecContext, node: PassNode, keys: list):
    """Cohort-wide witness stream (ops/wgl_stream.py): proves keys
    only; everything else falls through the unknown edge."""
    from ..ops.wgl_stream import check_wgl_witness_stream

    ctx.start_clock()
    kw: dict = {}
    if node.knobs.get("segment") is not None:
        kw["segment_keys"] = node.knobs["segment"]
    if node.knobs.get("max_restarts") is not None:
        kw["max_restarts"] = node.knobs["max_restarts"]
    limit = (ctx.lin.time_limit_s if ctx.mode == "cohort"
             else ctx.budget_left())
    # Device resource errors degrade inside the stream tier (recorded
    # as wgl.degrade.stream.*); anything else is a bug and propagates.
    stream_v = check_wgl_witness_stream(
        [ctx.packs[k] for k in keys], ctx.pm,
        time_limit_s=limit, **kw,
    )
    decided: dict = {}
    rest = []
    for k, v in zip(keys, stream_v):
        if v is True:
            decided[k] = {
                "valid": True,
                "algorithm": "wgl-tpu-stream",
                "configs-explored": int(ctx.packs[k].n_ok),
            }
        else:
            rest.append(k)
    if ctx.mode == "cohort" and telemetry.enabled():
        telemetry.count("wgl.settle.stream-proven", len(decided))
    pmemo = plan_cache.active_memo()
    if pmemo is not None and decided:
        from ..parallel.independent import _sanitize_settle

        for k, r in decided.items():
            pmemo.put(ctx.pmemo_key(k), _sanitize_settle(r))
    return decided, ({"unknown": rest} if rest else {})


def _run_screen(ctx: ExecContext, node: PassNode, keys: list):
    """Refutation screens (checker/refute.py).  Two modes: "classify"
    (cohort — a firing screen routes the key to the detail pass for a
    certificate) and "decide" (packs — the screen's exact refutation IS
    the verdict, no detail pass follows)."""
    from ..checker.refute import check_refute
    from ..utils import bounded_pmap

    decide = node.knobs.get("mode") == "decide"

    def screen_one(k):
        b = ctx.budget_left()
        try:
            return check_refute(
                ctx.packs[k], ctx.pm,
                time_limit_s=30.0 if b is None else min(b, 30.0),
            )
        except Exception:  # noqa: BLE001 — a screen bug must not
            log.warning("refutation screen failed for key %r", k,
                        exc_info=True)
            return None  # change a verdict; the search tiers decide

    screened = dict(zip(keys, bounded_pmap(screen_one, keys,
                                           bound=ctx.bound)))
    decided: dict = {}
    refuted, unknown = [], []
    for k in keys:
        ref = screened[k]
        if ref is None:
            unknown.append(k)
        elif decide:
            r: dict = {
                "valid": ref.valid,
                "algorithm": "refute-screen",
                "configs-explored": int(ref.configs_explored),
            }
            if ref.valid == "unknown" and ref.reason:
                r["reason"] = ref.reason
            decided[k] = r
        else:
            ctx.note(k)["screen_fired"] = True
            refuted.append(k)
    routed: dict = {}
    if refuted:
        routed["refuted"] = refuted
    if unknown:
        routed["unknown"] = unknown
    return decided, routed


def _run_batched(ctx: ExecContext, node: PassNode, keys: list):
    """Batched frontier BFS (ops/wgl_batched.py) over screen
    survivors.  True is proven; False is an exact device refutation
    routed to the detail pass; None (overflow/budget) falls through."""
    from ..ops.wgl_batched import check_wgl_batched

    if not keys:
        return {}, {}
    lin = ctx.lin
    beam = node.knobs.get("beam") or min(lin.beam, 32)
    batch = check_wgl_batched(
        [ctx.packs[k] for k in keys],
        ctx.pm,
        beam=beam,
        max_beam=max(lin.max_beam, lin.beam),
        mesh=ctx.mesh,
        time_limit_s=ctx.budget_left(),
    )
    decided: dict = {}
    refuted, unknown = [], []
    n_proven = 0
    for i, k in enumerate(keys):
        v = batch.valid[i]
        n = ctx.note(k)
        n["device_verdict"] = v
        n["device_explored"] = int(batch.explored[i])
        if v is True:
            decided[k] = {
                "valid": True,
                "algorithm": "wgl-tpu-batched",
                "configs-explored": int(batch.explored[i]),
            }
            n_proven += 1
        elif v is False:
            refuted.append(k)
        else:
            unknown.append(k)
    ctx.count("batched-proven", n_proven)
    routed: dict = {}
    if refuted:
        routed["refuted"] = refuted
    if unknown:
        routed["unknown"] = unknown
    return decided, routed


def _run_settle_exact(ctx: ExecContext, node: PassNode, keys: list):
    """The parallel CPU settle: screen-refuted keys re-derive their
    certificate, device-refuted keys get a small detail slice (the
    exact device verdict stands if it expires), unknowns go to the
    exact engine — the legacy settle_one, verbatim."""
    from ..checker.core import check_safe
    from ..checker.linearizable import Linearizable
    from ..utils import bounded_pmap

    lin, model = ctx.lin, ctx.model
    detail_budget = getattr(
        ctx.checker, "REFUTED_DETAIL_BUDGET_S", 10.0
    )

    def settle_one(k):
        n = ctx.notes.get(k) or {}
        dv = n.get("device_verdict")
        budget = ctx.budget_left()
        if dv is False:
            budget = (detail_budget if budget is None
                      else min(budget, detail_budget))
        single = Linearizable(
            model, "settle",
            time_limit_s=budget,
            max_configs=lin.max_configs,
        )
        r = check_safe(single, ctx.test, ctx.subs[k],
                       {**ctx.opts, "history_key": k})
        if dv is not None:
            r["device-verdict"] = dv
        if dv is False:
            if r.get("valid") == "unknown":
                # The detail slice expired; the device refutation is
                # exact (search exhausted without overflow) and
                # settles the verdict on its own.
                r = {
                    "valid": False,
                    "algorithm": "wgl-tpu-batched",
                    "configs-explored": n.get("device_explored", 0),
                    "device-verdict": False,
                }
            elif r.get("valid") is True:
                # Exact engines disagreeing is a checker bug, not a
                # history property; surface it loudly and keep the
                # CPU verdict (parity with per-key exact checking).
                log.error(
                    "device/CPU verdict mismatch on key %r: batched"
                    " kernel proved invalid, exact engine proved "
                    "valid — keeping the CPU verdict", k,
                )
        return r

    decided = dict(zip(keys, bounded_pmap(settle_one, keys,
                                          bound=ctx.bound)))
    for k in decided:
        n = ctx.notes.get(k) or {}
        if n.get("device_verdict") is False:
            ctx.count("batched-refuted")
        elif n.get("screen_fired"):
            ctx.count("screen-refuted")
        else:
            ctx.count("cpu-settled")
    return decided, {}


def _run_packs_exact(ctx: ExecContext, node: PassNode, keys: list):
    """Exact CPU engine over wire-packed submissions (the checkerd
    `_settle_packs` tail: no subs, no batched tier)."""
    decided = {}
    for k in keys:
        res, engine = ctx.lin._cpu_exact(
            ctx.packs[k], ctx.pm, "auto", time_limit_s=ctx.budget_left()
        )
        r: dict = {
            "valid": res.valid,
            "algorithm": engine,
            "configs-explored": int(res.configs_explored),
        }
        if res.valid == "unknown" and res.reason:
            r["reason"] = res.reason
        decided[k] = r
    return decided, {}


def _run_device_ladder(ctx: ExecContext, node: PassNode, keys: list):
    """The whole single-history device-first ladder of
    Linearizable._device_first (witness + frontier search, degradation
    safety nets, exact settling) as one exact pass."""
    decided = {}
    for k in keys:
        decided[k] = ctx.lin._device_first(
            ctx.packs[k], ctx.pm, ctx.model, ctx.lin.algorithm,
            ctx.test, ctx.opts,
        )
    return decided, {}


def _run_elle_cycles(ctx: ExecContext, node: PassNode, keys: list):
    """Elle dependency-cycle pass (checker/elle/graph.py), device-
    screened via the MXU transitive closure when asked.  `ctx.packs`
    carries DepGraphs; a found cycle refutes, an empty result proves
    acyclicity — exact, but registered can-refute because the anomaly
    interpretation belongs to the calling analysis."""
    decided = {}
    for k in keys:
        g = ctx.packs[k]
        if node.knobs.get("device") == "off":
            from ..checker.elle.graph import check_cycles

            decided[k] = {"cycles": check_cycles(g)}
        else:
            from ..ops.scc import check_cycles_device

            decided[k] = {"cycles": check_cycles_device([g])[0]}
    return decided, {}


def _register_builtins() -> None:
    for fam in (
        PassFamily("host-fallback", "exact", "host", _run_host_fallback,
                   doc="host-model search for unpackable keys"),
        PassFamily("online-consume", "can-prove-valid", "host",
                   _run_online,
                   doc="digest-gated streaming-session verdicts"),
        PassFamily("persistent-memo", "exact", "host", _run_pmemo,
                   doc="journaled plan-memo replay (cache.py)"),
        PassFamily("length-router", "exact", "host", _run_length_router,
                   knob_spec=("threshold",),
                   doc="routes only; decides nothing"),
        PassFamily("single-device", "exact", "device",
                   _run_single_device,
                   doc="per-key wgl-tpu ladder for long keys"),
        PassFamily("stream-witness", "can-prove-valid", "device",
                   _run_stream, knob_spec=("segment", "max_restarts"),
                   doc="ops/wgl_witness over one barrier stream "
                       "(ops/wgl_stream frontier)"),
        PassFamily("refute-screen", "can-refute", "host", _run_screen,
                   knob_spec=("mode",),
                   doc="checker/refute.py sound screens"),
        PassFamily("batched-bfs", "exact", "device", _run_batched,
                   knob_spec=("beam",),
                   doc="ops/wgl_batched vmapped frontier BFS"),
        PassFamily("settle-exact", "exact", "host", _run_settle_exact,
                   doc="wgl_cpu / wgl_event via the settle algorithm"),
        PassFamily("packs-exact", "exact", "host", _run_packs_exact,
                   doc="exact CPU engine over wire-packed tensors"),
        PassFamily("device-ladder", "exact", "device",
                   _run_device_ladder,
                   doc="single-history device-first ladder"),
        PassFamily("elle-cycles", "can-refute", "device",
                   _run_elle_cycles, knob_spec=("device",),
                   doc="elle SCC/cycle pass (ops/scc.py MXU closure)"),
    ):
        register_family(fam)


_register_builtins()


# ---------------------------------------------------------------------------
# Group scope: the settle-memo mechanic
# ---------------------------------------------------------------------------


class _GroupState:
    def __init__(self) -> None:
        self.groups: "OrderedDict[str, list]" = OrderedDict()
        self.group_result: dict[str, dict] = {}
        self.key_digest: dict[Any, str] = {}
        self.reps: list = []
        self.n_memo = 0


def _enter_group(ctx: ExecContext, keys: list) -> _GroupState:
    """Digest-groups the keys and replays memoized verdicts: the
    in-memory settle memo first (exactly the legacy ladder), then the
    persistent plan memo (which also warms the in-memory one)."""
    from ..parallel.independent import _memo_get, _memo_put

    gs = _GroupState()
    for k in keys:
        gs.groups.setdefault(ctx.digest(k), []).append(k)
    pmemo = plan_cache.active_memo()
    for d, members in gs.groups.items():
        hit = _memo_get(d)
        if hit is None and pmemo is not None:
            ph = pmemo.get(plan_cache.memo_key(d, ctx.identity))
            if ph is not None:
                hit = ph
                _memo_put(d, ph)
        if hit is not None:
            gs.group_result[d] = hit
        else:
            rep = members[0]
            gs.key_digest[rep] = d
            gs.reps.append(rep)
    gs.n_memo = sum(len(gs.groups[d]) for d in gs.group_result)
    return gs


def _memo_store(ctx: ExecContext, digest: str, r: dict) -> None:
    from ..parallel.independent import _memo_put, _sanitize_settle

    _memo_put(digest, r)
    if r.get("valid") in (True, False):
        pmemo = plan_cache.active_memo()
        if pmemo is not None:
            pmemo.put(plan_cache.memo_key(digest, ctx.identity),
                      _sanitize_settle(r))


def _fanout(ctx: ExecContext, gs: _GroupState) -> dict:
    """Every group's verdict to every member: the representative keeps
    the full result (its positional certificates cite ITS history
    slice); other members share the sanitized verdict."""
    from ..parallel.independent import _sanitize_settle

    live = set(gs.key_digest.values())
    settled: dict = {}
    for d, members in gs.groups.items():
        r = gs.group_result.get(d)
        if r is None:  # defensive: unreachable
            continue
        if d in live:
            settled[members[0]] = r
            extra = members[1:]
            gs.n_memo += len(extra)
        else:
            extra = members  # cross-call memo hit: all share
        for k2 in extra:
            shared = _sanitize_settle(r)
            shared["memo-hit"] = True
            settled[k2] = shared
    return settled


# ---------------------------------------------------------------------------
# The sweep
# ---------------------------------------------------------------------------


def execute(plan: Plan, ctx: ExecContext,
            seeds: Optional[dict] = None) -> dict:
    """Runs a plan to completion; returns {key: result}."""
    telemetry.count("wgl.plan.execute")
    results: dict = {}
    work: dict[str, list] = {nid: [] for nid in plan.nodes}
    for nid, ks in (seeds or {}).items():
        work[nid].extend(ks)

    nodes = list(plan)
    pre = [n for n in nodes if not n.group]
    grp = [n for n in nodes if n.group]

    def route(node: PassNode, routed: dict) -> None:
        for label, ks in routed.items():
            if not ks:
                continue
            tgt = node.target(label)
            if tgt is None:
                # A plan without a fallback edge leaves keys
                # undecided — sound, but worth recording.
                for k in ks:
                    results[k] = {
                        "valid": "unknown",
                        "error": f"plan: no {label!r} route out of "
                                 f"node {node.id!r}",
                    }
                telemetry.count("wgl.plan.unrouted", len(ks))
            else:
                work[tgt].extend(ks)

    for node in pre:
        keys = work.get(node.id) or []
        if not keys:
            continue
        telemetry.count("wgl.plan.pass-runs")
        fam = family(node.family)
        with telemetry.span(fam.span):
            decided, routed = fam.runner(ctx, node, keys)
        results.update(decided)
        route(node, routed)

    if grp:
        gkeys = work.get(grp[0].id) or []
        if gkeys:
            results.update(
                _execute_group(ctx, grp, gkeys, work, route)
            )
    return results


def _execute_group(ctx: ExecContext, grp: list, gkeys: list,
                   work: dict, route: Callable) -> dict:
    # One cost record for the whole settle pipeline (cohort mode only —
    # the legacy packs path records no settle-level profile either);
    # the chained span hook folds the batched children's compile/
    # execute time into this record, keeping the cost-model training
    # set shape identical across JEPSEN_PLAN values.
    cap = (
        profile.capture(
            "settle", keys=len(gkeys),
            ops=int(sum(ctx.packs[k].n for k in gkeys)),
        )
        if ctx.mode == "cohort"
        else contextlib.nullcontext(None)
    )
    with cap as _ps:
        gs = _enter_group(ctx, gkeys)
        work[grp[0].id] = list(gs.reps)
        for node in grp:
            keys = work.get(node.id) or []
            if not keys:
                continue
            telemetry.count("wgl.plan.pass-runs")
            fam = family(node.family)
            with telemetry.span(fam.span):
                decided, routed = fam.runner(ctx, node, keys)
            for k, r in decided.items():
                d = gs.key_digest[k]
                gs.group_result[d] = r
                _memo_store(ctx, d, r)
            route(node, routed)
        settled = _fanout(ctx, gs)
        if ctx.mode == "cohort":
            n_screen = ctx.counts.get("screen-refuted", 0)
            n_bp = ctx.counts.get("batched-proven", 0)
            n_br = ctx.counts.get("batched-refuted", 0)
            n_cpu = ctx.counts.get("cpu-settled", 0)
            if telemetry.enabled():
                telemetry.count("wgl.settle.screen-refuted", n_screen)
                telemetry.count("wgl.settle.batched-proven", n_bp)
                telemetry.count("wgl.settle.batched-refuted", n_br)
                telemetry.count("wgl.settle.cpu-settled", n_cpu)
                telemetry.count("wgl.settle.memo-hit", gs.n_memo)
            if _ps is not None:
                _ps.outcome = {
                    "screen-refuted": n_screen,
                    "batched-proven": n_bp,
                    "batched-refuted": n_br,
                    "cpu-settled": n_cpu,
                    "memo-hit": gs.n_memo,
                }
    return settled
