"""The double-buffered online checking session.

Dataflow::

    interpreter threads                checker thread (daemon)
    ───────────────────                ───────────────────────
    journal ──► feed(op) ──► buffer A        buffer B ──► ingest
                  (append under lock)          │  per-key PackedBuilder
                                               │  quiet keys ─► stream
                         swap every            │  witness batch (device)
                         ~50 ms / 2048 ops ◄───┘  big streams ─► frontier
                                                  advance (device)

One buffer fills on the host while the other's ops are routed, packed
and checked against the device — the generate/interpret side never
blocks on checking, and the checking side always has a full batch to
amortize H2D transfer over.

Verdicts are recorded against the packed digest of the key's history
at proof time (`parallel.independent._settle_digest`).  At analyze,
the post-hoc checkers re-pack each key and consume a verdict only when
digests match — a key that received ops after its proof is re-proven
or falls back, never served stale.  A consumed verdict also
invalidates nothing; a DROPPED one (key changed after proof) evicts
its settle-memo entry via `invalidate_settle_memo` so the cross-run
cohort can't replay it either.

The session is fail-open everywhere: any internal error marks it
broken, feed() becomes a no-op, and analyze simply finds no verdicts
to consume — online checking can cost latency, never the verdict.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Optional

from .. import telemetry
from ..telemetry import profile
from ..history.core import Op
from ..history.packed import PackedBuilder
from ..models.base import PackedModel
from .frontier import FrontierCarry

log = logging.getLogger(__name__)

#: Swap the buffers at least this often even when the run is slow.
SWAP_INTERVAL_S = 0.05
#: ...and as soon as this many ops are waiting.
SWAP_OPS = 2048
#: Single-stream mode: replan+advance the frontier only after this many
#: new stable rows (each advance replans the whole prefix on host, so
#: this bounds total planning work to O(n^2 / ADVANCE_ROWS)).
ADVANCE_ROWS = 32768
#: Keyed mode: a key whose builder exceeds this many rows graduates
#: from batched whole-key rechecks to its own FrontierCarry.
FRONTIER_ROWS = 65536
#: Keyed mode: don't re-prove a still-growing key until it has at least
#: this many rows more than at its last proof.
RECHECK_MIN_ROWS = 256


class DoubleBuffer:
    """The host half of the pipeline: `put` appends to the filling
    list, `take` swaps it out whole.  Contention is one lock around a
    list append — the interpreter side never waits on checking."""

    __slots__ = ("_lock", "_filling")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._filling: list = []

    def put(self, item: Any) -> int:
        with self._lock:
            self._filling.append(item)
            return len(self._filling)

    def take(self) -> list:
        with self._lock:
            batch, self._filling = self._filling, []
            return batch


class StreamingSession:
    """Online checker for one run.  `feed(op)` from the interpreter's
    journal; `finish()` once the run ends (drains, finalizes, measures
    verdict lag); `consume(key, digest)` from the post-hoc checkers.

    Mode is auto-detected from the first client invoke: a `KV` payload
    means a keyed (independent) workload with per-key builders and
    batched stream-witness proofs; anything else means one stream
    checked by a single incremental `FrontierCarry`.
    """

    MODE_KEYED = "keyed"
    MODE_SINGLE = "single"

    def __init__(
        self,
        pm: PackedModel,
        *,
        swap_interval_s: float = SWAP_INTERVAL_S,
        swap_ops: int = SWAP_OPS,
        advance_rows: int = ADVANCE_ROWS,
        frontier_rows: int = FRONTIER_ROWS,
        recheck_min_rows: int = RECHECK_MIN_ROWS,
        remote: Optional[Any] = None,
        run_id: str = "run",
    ):
        self.pm = pm
        self.swap_interval_s = swap_interval_s
        self.swap_ops = swap_ops
        self.advance_rows = advance_rows
        self.frontier_rows = frontier_rows
        self.recheck_min_rows = recheck_min_rows
        self.run_id = run_id

        self.mode: Optional[str] = None
        self.finished = False
        self.broken = False
        self.broken_reason: Optional[str] = None
        self.verdict_lag_s: Optional[float] = None

        self._buf = DoubleBuffer()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

        #: ops seen before the first client invoke (mode unknown);
        #: replayed ahead of the batch that decides the mode.
        self._carry: list = []
        # keyed mode
        self._pending: dict = {}            # process -> key (in-flight route)
        self._builders: dict = {}           # key -> PackedBuilder
        self._changed: dict = {}            # key -> True (ops since last check)
        self._checked_rows: dict = {}       # key -> n_rows at last attempt
        self._frontiers: dict = {}          # key -> FrontierCarry (big keys)
        self._fr_rows: dict = {}            # key -> n_rows at last advance
        # single mode
        self._builder: Optional[PackedBuilder] = None
        self._frontier: Optional[FrontierCarry] = None
        self._adv_rows = 0

        #: key (or None for single-stream) -> {"digest": str, "res": dict}
        self._verdicts: dict = {}
        #: key -> digest of the pack at its last witness attempt.  The
        #: witness is deterministic, so an identical pack can only
        #: repeat the same answer — finalize skips those (the big win:
        #: invalid keys restart the stream engine every attempt, and
        #: re-attempting them at finish() would put that cost straight
        #: into the verdict lag).
        self._attempted: dict = {}
        #: largest total row count a single mid-run stream batch has
        #: carried — the witness engine compiled buckets for that
        #: shape, so finalize chunks to it (wgl_witness buckets both
        #: the window and the block count; one oversized finalize pass
        #: would pay a fresh XLA compile seconds before the verdict).
        self._stream_rows_hwm = 0

        self._ops_ingested = 0
        self._swaps = 0
        self._checks = 0
        self._rechecks = 0

        #: streaming/remote.py RemoteFeed, already configured to mirror
        #: the submission RemoteChecker would make, or None.
        self._remote = remote

        self._thread = threading.Thread(
            target=self._loop, name="streaming-checker", daemon=True
        )
        self._thread.start()

    # -- producer side (interpreter threads) --------------------------------

    def feed(self, op: Op) -> None:
        """Appends one journal op.  Cheap and non-blocking; called from
        the interpreter's worker threads."""
        if self.broken or self.finished:
            return
        if self._buf.put(op) >= self.swap_ops:
            self._wake.set()

    # -- checker thread ------------------------------------------------------

    def _loop(self) -> None:
        while not self._stop.is_set():
            self._wake.wait(self.swap_interval_s)
            self._wake.clear()
            batch = self._buf.take()
            if not batch:
                continue
            try:
                self._ingest(batch)
            except Exception as e:  # noqa: BLE001
                self._break(f"{type(e).__name__}: {e}")

    def _break(self, reason: str) -> None:
        self.broken = True
        self.broken_reason = reason
        telemetry.count("wgl.online.broken")
        log.warning("streaming session broken, falling back post-hoc: %s",
                    reason)

    def _ingest(self, batch: list) -> None:
        self._swaps += 1
        self._ops_ingested += len(batch)
        telemetry.count("wgl.online.ops-ingested", len(batch))
        with telemetry.span("wgl.online.swap", ops=len(batch)):
            if self.mode is None:
                if self._carry:
                    batch = self._carry + batch
                    self._carry = []
                self._detect_mode(batch)
            if self.mode == self.MODE_KEYED:
                self._ingest_keyed(batch)
            elif self.mode == self.MODE_SINGLE:
                self._ingest_single(batch)
            else:
                # No client invoke yet (nemesis/info noise): hold the
                # ops, in order, until the mode-deciding invoke lands.
                self._carry = batch

    def _detect_mode(self, batch: list) -> None:
        from ..parallel.independent import KV

        for op in batch:
            if op.is_invoke and op.is_client_op:
                self.mode = (self.MODE_KEYED if isinstance(op.value, KV)
                             else self.MODE_SINGLE)
                log.info("streaming session: %s mode", self.mode)
                if self.mode == self.MODE_SINGLE:
                    self._builder = PackedBuilder(self.pm.encode)
                    self._frontier = FrontierCarry(self.pm)
                return

    # -- keyed (independent) mode -------------------------------------------

    def _route(self, o: Op):
        """Mirrors `parallel.independent.subhistories` exactly — same
        pending map, same KV unwrap, same drops — so the per-key op
        sequences (and hence packed digests) match what the post-hoc
        checker derives from the full history."""
        from ..parallel.independent import KV

        val = o.value
        if isinstance(val, KV):
            if o.is_invoke:
                self._pending[o.process] = val.key
            else:
                self._pending.pop(o.process, None)
            return val.key, o.replace(value=val.value)
        if (not o.is_invoke) and o.process in self._pending:
            return self._pending.pop(o.process), o.replace(value=val)
        return None, None

    def _ingest_keyed(self, batch: list) -> None:
        # Route scalar (the pending map is inherently sequential), then
        # ingest columnar: one append_many per touched key.
        routed_by_key: dict = {}
        for op in batch:
            k, routed = self._route(op)
            if routed is None:
                continue
            lst = routed_by_key.get(k)
            if lst is None:
                lst = routed_by_key[k] = []
            lst.append(routed)
        touched = {}
        for k, rops in routed_by_key.items():
            b = self._builders.get(k)
            if b is None:
                b = self._builders[k] = PackedBuilder(self.pm.encode)
            b.append_many(rops)
            touched[k] = True
            if self._remote is not None:
                self._remote.put_many(k, rops)
        for k in touched:
            self._changed[k] = True
            v = self._verdicts.pop(k, None)
            if v is not None:
                # The key grew past its proof: the recorded verdict —
                # and any memoized copy — describes a history that no
                # longer exists.
                self._invalidate(v["digest"])
                self._rechecks += 1
                telemetry.count("wgl.online.rechecks")
        self._advance_big_keys(touched)
        self._check_quiet_keys()

    def _invalidate(self, digest: str) -> None:
        from ..parallel.independent import invalidate_settle_memo

        invalidate_settle_memo(digest)

    def _advance_big_keys(self, touched: dict) -> None:
        """Keys too large for whole-key rechecks carry their own
        frontier, advanced as their stable prefix grows."""
        for k in touched:
            b = self._builders[k]
            if b.n_rows < self.frontier_rows:
                continue
            fr = self._frontiers.get(k)
            if fr is None:
                fr = self._frontiers[k] = FrontierCarry(self.pm)
                self._fr_rows[k] = 0
                telemetry.count("wgl.online.key-frontiers")
            if fr.dead:
                continue
            if b.n_rows - self._fr_rows[k] >= self.advance_rows:
                packed, s = b.snapshot()
                fr.advance(packed, s)
                self._fr_rows[k] = b.n_rows

    def _check_quiet_keys(self) -> None:
        """Batches every changed, currently-quiet key through one
        stream-witness pass and records proofs by digest."""
        quiet = []
        for k in list(self._changed):
            b = self._builders[k]
            if k in self._frontiers:
                continue  # frontier keys conclude at finish()
            if b.in_flight > 0:
                continue
            if k in self._checked_rows and \
                    b.n_rows - self._checked_rows[k] < self.recheck_min_rows:
                continue
            quiet.append(k)
        if not quiet:
            return
        packs = []
        for k in quiet:
            packs.append(self._builders[k].snapshot()[0])
            self._checked_rows[k] = self._builders[k].n_rows
            del self._changed[k]
        self._stream_batch(quiet, packs)

    def _stream_batch(self, keys: list, packs: list) -> None:
        """One stream-witness pass over per-key packs; proofs recorded
        against each pack's digest."""
        from ..ops.wgl_stream import check_wgl_witness_stream
        from ..parallel.independent import _memo_put, _settle_digest

        self._checks += 1
        telemetry.count("wgl.online.keys-checked", len(keys))
        digests = [_settle_digest(p, self.pm) for p in packs]
        self._stream_rows_hwm = max(self._stream_rows_hwm,
                                    sum(int(p.n) for p in packs))
        from ..plan import costmodel
        knobs, _src = costmodel.choose_stream_knobs(
            len(packs), sum(int(p.n) for p in packs))
        verdicts = check_wgl_witness_stream(
            packs, self.pm, segment_keys=knobs["segment"],
            max_restarts=knobs["max_restarts"],
        )
        for k, d, v in zip(keys, digests, verdicts):
            self._attempted[k] = d
            if v is True:
                res = {"valid": True, "algorithm": "wgl-online"}
                self._verdicts[k] = {"digest": d, "res": res}
                _memo_put(d, res)

    # -- single-stream mode ---------------------------------------------------

    def _ingest_single(self, batch: list) -> None:
        from ..parallel.independent import KV

        b = self._builder
        for op in batch:
            if isinstance(op.value, KV):
                self._break("KV op in single-stream mode")
                return
        b.append_many(batch)
        fr = self._frontier
        if fr is not None and not fr.dead and \
                b.n_rows - self._adv_rows >= self.advance_rows:
            packed, s = b.snapshot()
            fr.advance(packed, s)
            self._adv_rows = b.n_rows

    # -- completion ------------------------------------------------------------

    def finish(self) -> dict:
        """Stops the checker thread, drains the last buffer, runs the
        final proofs, and measures the verdict lag (time from the last
        op to the last online verdict).  Idempotent."""
        if self.finished:
            return self.stats()
        t0 = time.monotonic()
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=60.0)
        self.finished = True
        if not self.broken:
            try:
                # The frontier/session cost record: final-drain device
                # work (wgl.online.chunk spans) folds in via the span
                # hook; mid-run device time rides as a feature from the
                # frontier carries (it ran on the checker thread).
                with profile.capture(
                    "frontier",
                    keys=len(self._builders) or 1,
                    ops=int(self._ops_ingested),
                ) as _pf:
                    _pf.knob(mode=self.mode,
                             advance_rows=self.advance_rows)
                    batch = self._buf.take()
                    if batch:
                        self._ingest(batch)
                    self._finalize()
                    frontiers = list(self._frontiers.values())
                    if self._frontier is not None:
                        frontiers.append(self._frontier)
                    _pf.feature(
                        checks=self._checks,
                        device_s=round(sum(
                            fr.device_s for fr in frontiers), 6),
                    )
                    _pf.outcome = {"proven": len(self._verdicts)}
            except Exception as e:  # noqa: BLE001
                self._break(f"{type(e).__name__}: {e}")
        if self._remote is not None:
            self._remote.commit(list(self._builders))
        self.verdict_lag_s = time.monotonic() - t0
        telemetry.gauge("wgl.online.verdict-lag-s", self.verdict_lag_s)
        # Also into the quantile ring: the SLO p95 rule and the
        # /metrics summary family threshold on the distribution over
        # recent sessions, not this one sample.
        try:
            from ..telemetry import timeseries

            timeseries.observe("wgl.online.verdict-lag-s",
                               self.verdict_lag_s)
        except Exception:  # noqa: BLE001 — observability is side output
            pass
        # The verdict-lag SLO samples the gauge the instant it lands:
        # a blown lag budget dumps its postmortem here, at finish time,
        # not on the next telemetry flush.
        try:
            from ..telemetry import slo

            slo.evaluate()
        except Exception:  # noqa: BLE001 — alerting is side output
            log.warning("SLO evaluation at finish failed", exc_info=True)
        return self.stats()

    def _finalize(self) -> None:
        from ..parallel.independent import _memo_put, _settle_digest

        if self.mode == self.MODE_SINGLE:
            final = self._builder.finish()
            d = _settle_digest(final, self.pm)
            fr = self._frontier
            if fr is not None and fr.finalize(final) is True:
                res = {"valid": True, "algorithm": "wgl-online",
                       "op-count": int(final.n)}
                self._verdicts[None] = {"digest": d, "res": res}
                _memo_put(d, res)
            return
        if self.mode != self.MODE_KEYED:
            return
        # Close every builder: in-flight ops become indeterminate rows,
        # exactly as pack_history will see them post-hoc.
        finals = {k: b.finish() for k, b in self._builders.items()}
        self._changed.clear()
        # Frontier keys first: their carry already covers most blocks,
        # the finalize pass only runs the tail.
        for k, fr in self._frontiers.items():
            final = finals[k]
            if fr.finalize(final) is True:
                d = _settle_digest(final, self.pm)
                res = {"valid": True, "algorithm": "wgl-online"}
                self._verdicts[k] = {"digest": d, "res": res}
                _memo_put(d, res)
        # One last stream batch over every unproven key, on the FINAL
        # packs (mid-run proofs recorded snapshot digests; for a key
        # that stayed quiet those equal the final digest, so its
        # verdict already matches and is skipped here).  Keys whose
        # final pack is byte-identical to their last witness attempt
        # are skipped too: the witness is deterministic, so the answer
        # can only repeat — and invalid keys in particular restart the
        # stream engine on every attempt, which would otherwise land
        # squarely in the verdict lag.
        rest, packs = [], []
        for k in self._builders:
            if k in self._verdicts or k in self._frontiers:
                continue
            d = _settle_digest(finals[k], self.pm)
            if self._attempted.get(k) == d:
                continue
            rest.append(k)
            packs.append(finals[k])
        # Chunk sizing: by default HALF the mid-run high-water mark —
        # every mid-run batch already compiled its shape buckets, and
        # the window the witness buckets by scales with rows for
        # concatenated independent keys; a chunk at exactly the
        # high-water mark sits on the bucket edge, where one extra
        # indeterminate row tips into the next power of two and pays a
        # fresh XLA compile seconds before the verdict.  With a trained
        # cost model whose roofline-annotated stream records cover the
        # candidate shape buckets, the model picks the chunk rows
        # instead (plan/costmodel.py choose_finalize_chunk_rows); out of
        # support it falls back to the same halving formula.
        from ..plan import costmodel

        total_rows = sum(p.n for p in packs)
        cap, cap_src = costmodel.choose_finalize_chunk_rows(
            len(rest), total_rows, self._stream_rows_hwm
        )
        if cap_src == "model":
            telemetry.count("wgl.plan.finalize-chunk-model")
        else:
            telemetry.count("wgl.plan.finalize-chunk-heuristic")
        i = 0
        while i < len(rest):
            j, rows = i, 0
            while j < len(rest) and (j == i or rows + packs[j].n <= cap):
                rows += packs[j].n
                j += 1
            self._stream_batch(rest[i:j], packs[i:j])
            i = j

    # -- consumers (post-hoc checkers, analyze, bench) -------------------------

    def consume(self, key: Any, digest: str) -> Optional[dict]:
        """The online verdict for `key` (None = single-stream), iff its
        proof covers exactly the packed history whose digest the caller
        re-derived.  Returns a result dict or None."""
        v = self._verdicts.get(key)
        if v is None or v["digest"] != digest:
            return None
        telemetry.count("wgl.online.consumed")
        return dict(v["res"])

    def remote_ticket(self, addr: str, keys: list, model_spec: Any,
                      algorithm: str, budget_s: Any,
                      time_limit_s: Any) -> Optional[str]:
        """The checkerd ticket for this run's streamed upload, iff the
        upload completed and covered the same keys/config the caller
        would submit.  Lets RemoteChecker skip re-uploading a history
        the daemon already holds."""
        if self._remote is None:
            return None
        return self._remote.ticket_for(addr, keys, model_spec, algorithm,
                                       budget_s, time_limit_s)

    @property
    def proven(self) -> int:
        return len(self._verdicts)

    def stats(self) -> dict:
        """The results["streaming"] block."""
        out = {
            "mode": self.mode,
            "ops-ingested": self._ops_ingested,
            "swaps": self._swaps,
            "keys": (len(self._builders) if self.mode == self.MODE_KEYED
                     else (1 if self._builder is not None else 0)),
            "proven-online": len(self._verdicts),
            "rechecks": self._rechecks,
            "verdict-lag-s": self.verdict_lag_s,
        }
        if self.broken:
            out["broken"] = self.broken_reason
        fr = self._frontier
        if fr is not None:
            out["frontier"] = {
                "blocks": fr.blocks_done, "bars": fr.bars_done,
                "chunks": fr.chunks, "device-s": round(fr.device_s, 3),
                "dead": fr.dead_reason,
            }
        if self._frontiers:
            out["key-frontiers"] = len(self._frontiers)
        if self._remote is not None:
            out["remote"] = self._remote.stats()
        return out
