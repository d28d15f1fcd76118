"""The linearizable checker — knossos's role in the reference
(checker.clj:202-233), dispatching to the TPU frontier search or the CPU
reference by :algorithm:

  "wgl-tpu"     device beam search (ops/wgl.py); the exact CPU engine
                settles an unknown
  "wgl"         exact CPU search over packed ops
  "competition" device first, exact CPU to settle unknowns (mirrors
                knossos.competition racing its solvers)
  "settle"      cohort-settle entry (parallel/independent.py): the
                sound refutation screens first, then the auto-routed
                exact CPU engine — no device pass (the batched tiers
                already had their shot)

Models with no packed form fall back to the host-model search.
"""

from __future__ import annotations

from typing import Any, Optional

from .. import telemetry
from ..history.core import History
from ..telemetry import profile
from ..history.packed import pack_history
from ..models.base import Model, PackedModel
from .core import Checker
from .wgl_cpu import WGLResult, check_wgl_cpu, check_wgl_host_model

#: Budget for the exact settling pass when the device search returns
#: unknown and the checker has no configured time limit.  The round-2
#: gate (CPU_FALLBACK_MAX_OPS = 5_000: histories above it were NEVER
#: handed to the exact engine and stayed "unknown" forever) is gone —
#: the event-walk engine exists precisely for large info-heavy
#: histories, and budgets, not op counts, bound its cost.
DEFAULT_SETTLE_BUDGET_S = 120.0


class Linearizable(Checker):
    def __init__(
        self,
        model: Optional[Model] = None,
        algorithm: str = "wgl-tpu",
        *,
        beam: int = 1024,
        max_beam: int = 4096,
        block: int = 256,
        time_limit_s: Optional[float] = None,
        max_configs: int = 5_000_000,
        streaming: bool = True,
    ):
        self.model = model
        self.algorithm = algorithm
        self.beam = beam
        self.max_beam = max_beam
        self.block = block
        self.time_limit_s = time_limit_s
        self.max_configs = max_configs
        #: Consume an online verdict from a run's StreamingSession
        #: (jepsen_tpu/streaming/) when the whole-history digest
        #: matches.  Explicitly named engines ("wgl", "event", ...)
        #: ignore this — they are exercised as asked.
        self.streaming = streaming

    def check(self, test: dict, history: History, opts: dict) -> dict:
        from ..ops import degrade

        # Capture every degradation-ladder step taken on this thread
        # while checking, so the report shows not just which tier
        # produced the verdict ("algorithm") but the path taken to it.
        with degrade.capture() as steps:
            out = self._check(test, history, opts)
        if steps:
            out["degradations"] = steps
        return out

    def _check(self, test: dict, history: History, opts: dict) -> dict:
        model = self.model or test.get("model")
        if model is None:
            raise ValueError("linearizable checker needs a model")
        algorithm = self.algorithm

        try:
            pm = model.packed()
        except NotImplementedError:
            pm = None

        if pm is None:
            return self._host_fallback(history, model, "wgl-host", opts)

        try:
            with telemetry.span("ingest.pack"):
                packed = pack_history(history, pm.encode)
        except ValueError:
            # The history contains ops the packed form cannot encode
            # soundly (e.g. indeterminate dequeues): host model search.
            return self._host_fallback(
                history, model, "wgl-host-unpackable", opts
            )
        if pm.validate_packed is not None:
            reason = pm.validate_packed(packed)
            if reason is not None:
                return self._host_fallback(
                    history, model, "wgl-host-unpackable", opts,
                    reason=reason,
                )

        # Online verdict first, even over an explicitly named engine: a
        # streaming session (jepsen_tpu/streaming/) may have proven this
        # stream while the run was generating it, and a run that asked
        # for --streaming wants that proof consumed.  Digest-gated —
        # only when the finished pack equals what the proof covered —
        # and engine-naming tests never carry a session, so they still
        # exercise the engine they asked for.
        sess = (test or {}).get("streaming-session")
        if self.streaming and sess is not None:
            # The digest is computed in the SESSION's code space (its
            # encoder interned values in journal order; ours may have
            # assigned different codes) — see independent._online_digest.
            from ..parallel.independent import _online_digest

            d = _online_digest(sess, pm, history)
            r = sess.consume(None, d) if d is not None else None
            if r is not None:
                return r

        if algorithm in ("wgl", "linear", "cpu", "event"):
            # An explicitly named engine is exercised as asked (tests
            # and debugging depend on it); the screens only join the
            # strategy-picking paths below.
            res, engine = self._cpu_exact(packed, pm, algorithm)
            return self._render(res, packed, engine, model, pm, opts=opts)

        if algorithm == "settle":
            # Cohort-settle entry (parallel/independent.py): the device
            # tiers already had their shot, so this is screen-then-CPU —
            # the sound O(n log n) refutation screens decide the invalid
            # families that dominate practice (planted violations,
            # unsupported/stale reads) in milliseconds, and only the
            # rare survivor pays the exact engine.
            import time as _time

            from .refute import check_refute

            t0 = _time.monotonic()
            ref = check_refute(packed, pm, time_limit_s=self.time_limit_s)
            if ref is not None:
                return self._render(ref, packed, "refute-screen", model,
                                    pm, opts=opts)
            remaining = None
            if self.time_limit_s is not None:
                remaining = max(
                    1.0, self.time_limit_s - (_time.monotonic() - t0)
                )
            res, engine = self._cpu_exact(packed, pm,
                                          time_limit_s=remaining)
            return self._render(res, packed, engine, model, pm, opts=opts)

        # Compiled-plan route for the auto device paths: the same
        # ladder (_device_first) as a plan-executor pass, fronted by
        # the persistent plan memo when a cache directory is
        # configured.  Explicitly named engines above never route —
        # they are exercised as asked.
        from ..plan import enabled as _plan_enabled

        if _plan_enabled():
            try:
                from ..plan.compiler import run_single

                return run_single(self, packed, pm, model, algorithm,
                                  test, opts)
            except Exception:  # noqa: BLE001 — legacy ladder is the net
                import logging

                telemetry.count("wgl.plan.fallback")
                logging.getLogger(__name__).warning(
                    "plan executor failed; using the legacy ladder",
                    exc_info=True,
                )

        return self._device_first(packed, pm, model, algorithm, test,
                                  opts)

    def _device_first(self, packed, pm, model, algorithm: str,
                      test: dict, opts: dict) -> dict:
        """The device-first strategy chain: sound refutation screens,
        the frontier beam search with its degradation safety nets, and
        the exact CPU settling passes.  One sound, exact unit — the
        plan executor runs it as the `device-ladder` pass family."""
        # Sound non-linearizability screens (checker/refute.py) run
        # first on the device-first paths: O(n log n), exact-when-they-
        # fire, and the only engine that settles the invalid families
        # the exact searches can't reach at scale (the WGL closure is
        # exponential in concurrency once info ops unlock every state —
        # knossos hits the same wall).  knossos.competition races its
        # solvers the same way (checker.clj:214-233).
        import time as _time

        from .refute import check_refute

        t_start = _time.monotonic()
        with telemetry.span("wgl.screen"):
            ref = check_refute(packed, pm, time_limit_s=self.time_limit_s)
        if ref is not None:
            return self._render(ref, packed, "refute-screen", model, pm,
                                opts=opts)
        # One budget for the whole strategy chain: the screen's cost
        # (and everything after) comes out of the configured limit, so
        # per-key callers (parallel/independent.py) see at most ~1x
        # time_limit_s, not screen+device+settle each spending it anew.
        budget_left = None
        if self.time_limit_s is not None:
            budget_left = max(
                1.0, self.time_limit_s - (_time.monotonic() - t_start)
            )

        # Device-first paths.
        from ..ops import degrade
        from ..ops.wgl import check_wgl_device

        def _device(beam: int, max_beam: int, block: int, budget):
            return check_wgl_device(
                packed,
                pm,
                beam=beam,
                max_beam=max_beam,
                block=block,
                time_limit_s=budget,
                # "search-mesh" shards this ONE search's BFS frontier
                # across devices (the within-search axis).  It is a
                # distinct key from "mesh", which already means the
                # ACROSS-keys axis (parallel/independent.py) — the two
                # compose badly if conflated.
                mesh=(test or {}).get("search-mesh"),
                # Long-search checkpointing (SURVEY.md §5): when the
                # store gives this checker a directory, the witness
                # persists its inter-chunk carry there, and a
                # re-`analyze` after a kill or budget expiry resumes
                # instead of restarting.
                checkpoint_dir=(opts or {}).get("dir"),
            )

        def _budget_now():
            if self.time_limit_s is None:
                return None
            return max(1.0, self.time_limit_s - (_time.monotonic() - t_start))

        try:
            res = _device(self.beam, self.max_beam, self.block, budget_left)
        except Exception as e:
            if degrade.is_resource_error(e):
                # Safety net above the tiers' own ladders (a resource
                # error can surface outside their guarded call sites,
                # e.g. in a host-side table build): retry the whole
                # device search once at half size, then settle the
                # verdict on the exact CPU engine.
                degrade.record("dispatch", "retry-halved", e)
                try:
                    res = _device(
                        max(self.beam // 2, 64),
                        max(self.max_beam // 2, 64),
                        max(self.block // 2, 32),
                        _budget_now(),
                    )
                except Exception as e2:  # noqa: BLE001
                    if not degrade.is_resource_error(e2):
                        raise
                    degrade.record("dispatch", "fall-through", e2)
                    res, engine = self._cpu_exact(
                        packed, pm, time_limit_s=_budget_now()
                        if self.time_limit_s is not None
                        else DEFAULT_SETTLE_BUDGET_S,
                    )
                    return self._render(
                        res, packed, f"{engine}-degraded", model, pm,
                        opts=opts,
                    )
            else:
                # Anything else — a missing backend included — is an
                # error, not a reason to run the search elsewhere.
                raise
        used = "wgl-tpu"
        if res.valid is False and not res.final_configs:
            # The device BFS settles the verdict but carries no
            # counterexample detail; re-derive final configs on the CPU
            # for reporting + linear.svg (checker.clj:223-229).  This
            # pass is reporting-only, so it gets what remains of the
            # configured budget (capped when none is set) rather than a
            # fresh full one — the verdict stands either way.
            remaining = 30.0
            if budget_left is not None:
                remaining = max(1.0, budget_left - res.elapsed_s)
            cpu, _ = self._cpu_exact(packed, pm, time_limit_s=remaining)
            if cpu.valid is False:
                res = cpu
                used = "wgl-tpu+cpu-report"
        if res.valid == "unknown":
            # Settle with the exact engine regardless of history size
            # (knossos competition decides both directions,
            # checker.clj:214-233).  Governance is the time budget: the
            # configured limit's remainder, a default when none is set,
            # or — under "competition" — no limit at all, matching the
            # reference's race-to-a-verdict semantics.
            if algorithm == "competition":
                remaining = (
                    None if budget_left is None
                    else max(1.0, budget_left - res.elapsed_s)
                )
            elif budget_left is not None:
                remaining = max(1.0, budget_left - res.elapsed_s)
            else:
                remaining = DEFAULT_SETTLE_BUDGET_S
            cpu, _ = self._cpu_exact(packed, pm, time_limit_s=remaining)
            if cpu.valid != "unknown":
                res = cpu
                used = "wgl-tpu+cpu-fallback"
            else:
                budget_txt = (
                    "unbounded" if remaining is None
                    else f"{remaining:.1f}s"
                )
                reason = cpu.reason or res.reason or "search exhausted"
                res.reason = (
                    f"{reason} (exact settling pass budget "
                    f"{budget_txt} also exhausted)"
                )
        return self._render(res, packed, used, model, pm, opts=opts)

    def _host_fallback(self, history, model, label: str, opts,
                       reason=None) -> dict:
        res = check_wgl_host_model(
            history,
            model,
            max_configs=self.max_configs,
            time_limit_s=self.time_limit_s,
        )
        out = self._render(res, None, label, model, opts=opts)
        if reason is not None:
            out["packed-fallback-reason"] = reason
        return out

    def _cpu_exact(self, packed, pm, algorithm: str = "auto",
                   time_limit_s: Optional[float] = None):
        """The exact host search -> (result, engine-label): the
        event-walk with the info-class quotient (checker/wgl_event.py)
        when indeterminate ops are present — identity-based DFS
        memoization explodes on exactly those — else the memoized DFS.
        The time limit is a call argument, never instance mutation:
        one checker instance serves concurrent per-key threads
        (parallel/independent.py)."""
        from .wgl_event import check_wgl_event

        limit = self.time_limit_s if time_limit_s is None else time_limit_s
        with profile.capture(
            "exact-cpu", ops=int(packed.n), ok=int(packed.n_ok),
        ) as _pc:
            _pc.knob(max_configs=self.max_configs, time_limit_s=limit)
            if algorithm == "event" or (
                algorithm != "wgl" and packed.n > packed.n_ok
            ):
                res, engine = check_wgl_event(
                    packed,
                    pm,
                    max_configs=self.max_configs,
                    time_limit_s=limit,
                ), "event"
            else:
                res, engine = check_wgl_cpu(
                    packed,
                    pm,
                    max_configs=self.max_configs,
                    time_limit_s=limit,
                ), "wgl"
            _pc.knob(engine=engine)
            _pc.outcome = res.valid
            _pc.feature(explored=int(res.configs_explored))
        return res, engine

    def _render(
        self,
        res: WGLResult,
        packed,
        algorithm: str,
        model,
        pm: Optional[PackedModel] = None,
        opts: Optional[dict] = None,
    ) -> dict:
        out = {
            "valid": res.valid,
            "algorithm": algorithm,
            "configs-explored": res.configs_explored,
            "elapsed-s": round(res.elapsed_s, 6),
        }
        if res.reason:
            out["unknown-reason"] = res.reason
        if res.valid == "unknown" and res.final_configs:
            # The WGL death state for budget-blown unknowns: the
            # deepest configurations the search was holding when the
            # limit hit — forensics dossiers ship these even when
            # there is no refutation to shrink.
            out["final-configs"] = res.final_configs[:10]
        if res.valid is False and res.final_configs:
            # Truncate like checker.clj:230-233 (10 configs).
            out["final-configs"] = res.final_configs[:10]
            if (
                res.crashed_at is not None
                and packed is not None
                and pm is not None
            ):
                a = res.crashed_at
                desc = (
                    pm.describe_op(
                        int(packed.f[a]), int(packed.a0[a]), int(packed.a1[a])
                    )
                    if pm.describe_op
                    else None
                )
                out["crashed-op"] = {
                    "history-index": int(packed.src_index[a]),
                    "op": desc,
                }
            # Counterexample artifact, knossos's linear.svg
            # (checker.clj:223-229): drawn into the store dir when the
            # run gives us one.
            d = (opts or {}).get("dir")
            if d and packed is not None and pm is not None:
                import os

                from .linviz import render_analysis

                try:
                    os.makedirs(d, exist_ok=True)
                    path = render_analysis(
                        packed, pm, res, os.path.join(d, "linear.svg")
                    )
                    if path:
                        out["counterexample-file"] = path
                except OSError:
                    pass
        return out


def linearizable(model=None, algorithm: str = "wgl-tpu", **kw) -> Linearizable:
    return Linearizable(model, algorithm, **kw)
