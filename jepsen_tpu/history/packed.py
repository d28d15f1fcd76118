"""Packed columnar op tensors — the device-facing history representation.

This is the TPU-native serialization called for by BASELINE.json: a history
becomes packed int32 arrays (process, f, args, type) plus invocation /
completion event indices, ready to ship to device for checker kernels.
Mirrors what `jepsen.history`'s Op records + `knossos`'s history
preprocessing provide to the reference's checkers (SURVEY.md §2.4), but
columnar from the start.

Shapes: for a history with n live operations (invoke/completion pairs from
client ops, certain failures dropped), every column is an `(n,)` numpy
array sorted by invocation order — int32 for op payloads
(process/status/f/a0/a1), int64 for event bookkeeping (inv/ret/src_index/
preds/horizon, since ret uses NO_RET = int64 max; the device path clamps
to int32 INF on transfer).  Precedence structure is reduced to two
counters per op (SURVEY.md §7 stage 3; see ops/wgl.py for how the search
uses them):

  preds[a] = #{y != a : ret(y) < inv(a)}   ops that must precede a
  horizon[a] = #{y != a : inv(y) < ret(a)} last level at which a may remain
                                            un-linearized

Info (indeterminate) ops never complete, so ret = +inf (INT64 max) and
horizon = n-1: they stay optional forever — exactly why high-:info
histories blow up search width (SURVEY.md §7 "hard parts").
"""

from __future__ import annotations

from dataclasses import dataclass, field
from itertools import chain
from operator import attrgetter
from typing import Any, Callable, Optional

import numpy as np

from .core import FAIL, INFO, INVOKE, OK, History, Op

from .. import telemetry

#: Sentinel for "never returns" event index.
NO_RET = np.iinfo(np.int64).max

#: Sentinel int32 for missing / nil argument values.
NIL = np.iinfo(np.int32).min

#: Status codes for packed ops.
ST_OK = 1
ST_INFO = 3


class Interner:
    """Dense int interning of arbitrary hashable values (f symbols, large
    or non-int op payloads)."""

    __slots__ = ("values", "_ids")

    def __init__(self) -> None:
        self.values: list[Any] = []
        self._ids: dict[Any, int] = {}

    def intern(self, v: Any) -> int:
        i = self._ids.get(v)
        if i is None:
            i = len(self.values)
            self._ids[v] = i
            self.values.append(v)
        return i

    def value(self, i: int) -> Any:
        return self.values[i]

    def __len__(self) -> int:
        return len(self.values)


#: Column order + dtypes of the binary PackedOps serialization.  The
#: wire/rest format is just these arrays little-endian, concatenated
#: after a magic + u64 row count — no per-element framing, so a packed
#: history round-trips at memcpy speed (checkerd ships these frames).
PACKED_COLUMNS: tuple[tuple[str, Any], ...] = (
    ("inv", np.int64),
    ("ret", np.int64),
    ("process", np.int32),
    ("status", np.int32),
    ("f", np.int32),
    ("a0", np.int32),
    ("a1", np.int32),
    ("src_index", np.int64),
    ("preds", np.int64),
    ("horizon", np.int64),
)

PACKED_MAGIC = b"JPKD1\n"


def packed_to_bytes(p: "PackedOps") -> bytes:
    """Serializes a PackedOps to the columnar binary form."""
    parts = [PACKED_MAGIC, np.int64(p.n).tobytes()]
    for name, dtype in PACKED_COLUMNS:
        col = np.ascontiguousarray(getattr(p, name), dtype=dtype)
        if col.shape != (p.n,):
            raise ValueError(
                f"column {name}: shape {col.shape} != ({p.n},)"
            )
        parts.append(col.tobytes())
    return b"".join(parts)


def packed_from_bytes(buf: bytes) -> "PackedOps":
    """Inverse of packed_to_bytes.  Validates magic and total length so
    a torn or foreign frame raises instead of mis-slicing columns."""
    if buf[: len(PACKED_MAGIC)] != PACKED_MAGIC:
        raise ValueError("not a packed-ops frame (bad magic)")
    off = len(PACKED_MAGIC)
    n = int(np.frombuffer(buf, dtype=np.int64, count=1, offset=off)[0])
    if n < 0:
        raise ValueError(f"packed-ops frame: negative row count {n}")
    off += 8
    want = off + sum(n * np.dtype(dt).itemsize for _, dt in PACKED_COLUMNS)
    if len(buf) != want:
        raise ValueError(
            f"packed-ops frame: {len(buf)} bytes, want {want} for n={n}"
        )
    cols = {}
    for name, dtype in PACKED_COLUMNS:
        # .copy(): frombuffer views are read-only and pin the source
        # buffer; the checker mutates nothing but numpy ops want
        # writable, owned arrays.
        cols[name] = np.frombuffer(
            buf, dtype=dtype, count=n, offset=off
        ).copy()
        off += n * np.dtype(dtype).itemsize
    return PackedOps(**cols)


#: An encoder maps (invocation, completion|None) to packed
#: (f_code, a0, a1) int32 triple, or None to drop the op entirely (e.g.
#: indeterminate reads, which can never affect model state).
OpEncoderFn = Callable[[Op, Optional[Op]], Optional[tuple[int, int, int]]]


@dataclass
class PackedOps:
    """Columnar live-operation table, invocation-ordered."""

    #: (n,) invocation event index within the source history
    inv: np.ndarray
    #: (n,) completion event index, NO_RET when never completed
    ret: np.ndarray
    #: (n,) worker process ids
    process: np.ndarray
    #: (n,) ST_OK / ST_INFO
    status: np.ndarray
    #: (n,) packed op function codes
    f: np.ndarray
    #: (n,) first argument (NIL if absent)
    a0: np.ndarray
    #: (n,) second argument (NIL if absent)
    a1: np.ndarray
    #: (n,) original History index of the invocation (for reporting)
    src_index: np.ndarray
    #: (n,) number of ops that must be linearized before this one
    preds: np.ndarray
    #: (n,) last BFS level at which this op may remain un-linearized
    horizon: np.ndarray

    @property
    def n(self) -> int:
        return int(self.inv.shape[0])

    @property
    def n_ok(self) -> int:
        return int((self.status == ST_OK).sum())

    def op_row(self, a: int) -> dict[str, int]:
        return {
            "inv": int(self.inv[a]),
            "ret": int(self.ret[a]),
            "process": int(self.process[a]),
            "status": int(self.status[a]),
            "f": int(self.f[a]),
            "a0": int(self.a0[a]),
            "a1": int(self.a1[a]),
            "src_index": int(self.src_index[a]),
        }


class PackedBuilder:
    """Incremental `pack_history`: ops append one at a time (the
    interpreter's journal order) and chunks encode without re-packing
    the prefix — the streaming checker's ingest primitive
    (jepsen_tpu/streaming/).

    Equivalence contract (tested byte-for-byte in
    tests/test_histgen_packed.py): for any history h,

        b = PackedBuilder(encode)
        for o in h: b.append(o)
        packed_to_bytes(b.finish()) == packed_to_bytes(pack_history(h, encode))

    `append`/`_append_client`/`_emit` are the per-op reference: the
    client filter, the dense event enumeration, the FAIL/None-encode
    drops, the double-invoke and unfinished-op indeterminates, and the
    order in which rows reach the encoder (which fixes the interner's
    codes).  pack_history and append_many are the columnar form of the
    same state machine (`_pair`), tested byte-for-byte against it in
    tests/test_pack_columnar.py.

    Mid-run, `snapshot()` returns the STABLE ROW PREFIX: rows whose
    invocation event index is < s, where s = min invocation index over
    in-flight ops (ops invoked but not yet completed).  Every future
    row either belongs to an in-flight op (inv >= s) or to an op not
    yet invoked (inv >= the current event counter >= s), so it sorts
    AFTER the prefix — prefix row indices, contents and order are
    final.  That stability is what lets the frontier consumer
    (streaming/frontier.py) carry device state across chunks.
    """

    __slots__ = ("encode", "_e", "_pending", "_rows", "_stable",
                 "_finished", "_counted")

    def __init__(self, encode: OpEncoderFn):
        self.encode = encode
        #: next dense event index over CLIENT ops (pack_history's e).
        self._e = 0
        #: process -> (inv_e, invoke Op), exactly pack_history's pending.
        self._pending: dict[Any, tuple[int, Op]] = {}
        #: every emitted row tuple, in EMIT order (finish() sorts, so
        #: this matches pack_history's pre-sort rows list exactly).
        self._rows: list[tuple[int, int, int, int, int, int, int, int]] = []
        #: inv-sorted prefix of rows proven stable by a past snapshot().
        self._stable: list[tuple[int, int, int, int, int, int, int, int]] = []
        self._finished = False
        #: client events already flushed to the ingest.append.ops
        #: counter (append itself is too hot for per-op telemetry:
        #: deltas flush at snapshot/finish instead).
        self._counted = 0

    # -- introspection ------------------------------------------------------

    @property
    def n_events(self) -> int:
        """Client events consumed so far."""
        return self._e

    @property
    def n_rows(self) -> int:
        """Rows emitted so far (more may follow until finish())."""
        return len(self._rows) + len(self._stable)

    @property
    def in_flight(self) -> int:
        """Ops invoked but not yet completed."""
        return len(self._pending)

    def stable_bound(self) -> int:
        """s: the event index below which rows are final.  Equals the
        minimum in-flight invocation index, or the event counter when
        nothing is in flight (everything so far is stable)."""
        if not self._pending:
            return self._e
        return min(inv_e for inv_e, _ in self._pending.values())

    # -- ingest -------------------------------------------------------------

    def _emit(self, inv_e: int, invoke_op: Op, ret_e: int,
              comp: Optional[Op]) -> None:
        if comp is not None and comp.type == FAIL:
            return  # certainly never happened
        status = ST_OK if (comp is not None and comp.type == OK) else ST_INFO
        enc = self.encode(invoke_op, comp)
        if enc is None:
            return
        fc, a0, a1 = enc
        self._rows.append(
            (
                inv_e,
                ret_e if status == ST_OK else NO_RET,
                invoke_op.process,
                status,
                fc,
                a0,
                a1,
                invoke_op.index,
            )
        )

    def append(self, o: Op) -> None:
        """Feeds one op in journal order.  Non-client ops are ignored
        without consuming an event index (pack_history's client
        filter)."""
        if self._finished:
            raise RuntimeError("PackedBuilder already finished")
        if not o.is_client_op:
            return
        self._append_client(o)

    def extend(self, ops: "Any") -> None:
        """Feeds a chunk of ops (may be empty)."""
        self.append_many(ops)

    #: Below this many client ops the numpy pairing setup costs more
    #: than it saves; fall back to the scalar loop.
    _MANY_MIN = 16

    def append_many(self, ops: "Any") -> None:
        """Feeds a chunk of ops in journal order — byte-identical to
        calling append() per op (tests/test_pack_columnar.py), with the
        pairing done columnar (`_pair`) and the rows encoded in
        append()'s emit order.  The invocations pending from earlier
        appends enter the pairing as events before the chunk, in
        pending-dict order, so a chunk's first op per process pairs
        with or supersedes them like any other predecessor."""
        if self._finished:
            raise RuntimeError("PackedBuilder already finished")
        client = [o for o in ops if isinstance(o.process, int)]
        n = len(client)
        if n < self._MANY_MIN:
            for o in client:
                self._append_client(o)
            return
        e0 = self._e
        self._e = e0 + n
        pending = self._pending
        carried = list(pending.values())
        k = len(carried)
        run = [op for _, op in carried] + client
        events = np.concatenate([
            np.array([e for e, _ in carried], dtype=np.int64),
            np.arange(e0, e0 + n, dtype=np.int64),
        ])
        types, procs = _client_columns(run)
        inv, comp, tail, tail_start = _pair(types, procs)
        rows = _encode_rows(self.encode, run, types, procs, events, inv, comp)
        self._rows.extend(map(tuple, rows.tolist()))
        # A carried invocation stays where it is in the pending dict
        # only while its process has no completion in the chunk; the
        # rest enter in the order append() would insert them.
        stay = set(tail_start.tolist())
        for c in range(k):
            if c not in stay:
                del pending[run[c].process]
        for t in tail.tolist():
            pending[run[t].process] = (int(events[t]), run[t])

    def _append_client(self, o: Op) -> None:
        """append() minus the client filter (caller already checked)."""
        e = self._e
        self._e = e + 1
        if o.type == INVOKE:
            prev = self._pending.get(o.process)
            if prev is not None:
                self._emit(prev[0], prev[1], -1, None)
            self._pending[o.process] = (e, o)
        else:
            inv = self._pending.pop(o.process, None)
            if inv is None:
                return
            inv_e, inv_op = inv
            self._emit(inv_e, inv_op, e, o)

    # -- snapshots & finish -------------------------------------------------

    def _advance_stable(self, s: int) -> None:
        """Moves rows with inv < s from the unsorted tail into the
        inv-sorted stable prefix.  Sound because every previously
        stable row has inv < the previous s <= every newly stable
        row's inv: sorting the batch and appending keeps the whole
        prefix sorted."""
        if not self._rows:
            return
        fresh = [r for r in self._rows if r[0] < s]
        if not fresh:
            return
        self._rows = [r for r in self._rows if r[0] >= s]
        fresh.sort(key=lambda r: r[0])
        self._stable.extend(fresh)

    def discard_stable_prefix(
        self, *, bars_per_block: int, blocks_done: int
    ) -> tuple[int, int, int]:
        """Rolling-window discard: drops the longest prefix of the
        stable rows that the frontier consumer can never need again,
        renumbers the surviving event indices down to a dense range,
        and returns ``(rows_dropped, bars_dropped, event_shift)`` so
        the caller can `FrontierCarry.rebase()` in lockstep.

        A prefix of length d is discardable when:

          1. every dropped row is ST_OK — an ST_INFO row has
             ret = NO_RET and stays a candidate entrant of every
             future block, so it pins the discard point (documented
             limitation: an indeterminate op early in the run caps how
             much history can ever be dropped before an epoch restart);
          2. max(ret over the prefix) < min(ret over every retained
             stable OK row) — then the prefix's barriers are EXACTLY
             the global barrier ranks [0, d) (bars sort by ret), so
             retained bar ranks shift uniformly by d;
          3. d is a multiple of `bars_per_block` — block boundaries
             stay aligned after the shift;
          4. d <= (blocks_done - 1) * bars_per_block — the most recent
             PROCESSED block must stay resident, because the carried
             frontier window (`_prev_active`) references that block's
             own rows; discarding them would orphan the member matrix.

        Under those conditions every device-side comparison the
        frontier makes (bar rank vs k0, inv vs barrier ret, window
        regather by row index) is invariant under the uniform shift —
        tests/test_monitor.py asserts verdict byte-parity.

        Event renumbering (the returned `event_shift`) subtracts the
        minimum surviving event index from every retained inv/ret and
        from the event counter, so a paced week-long run never walks
        the int32 timeline off its cliff (~2.1e9 events)."""
        if self._finished:
            raise RuntimeError("PackedBuilder already finished")
        K = bars_per_block
        max_bars = max(0, (blocks_done - 1)) * K
        if K <= 0 or max_bars <= 0 or not self._stable:
            return 0, 0, 0
        # Longest all-OK prefix of the stable rows.
        n_ok_prefix = 0
        for r in self._stable:
            if r[3] != ST_OK:
                break
            n_ok_prefix += 1
        if n_ok_prefix == 0:
            return 0, 0, 0
        # Condition 2: the prefix must be ret-closed against every
        # retained OK row — stable tail AND unsorted tail (a row with
        # inv >= s may still have completed before a stable row did,
        # so tail rets compete for low barrier ranks too).  Pending
        # ops complete at future events > every existing ret.
        min_ret_rest = min(
            min(
                (r[1] for r in self._stable[n_ok_prefix:] if r[3] == ST_OK),
                default=NO_RET,
            ),
            min(
                (r[1] for r in self._rows if r[3] == ST_OK),
                default=NO_RET,
            ),
        )
        rets = sorted(r[1] for r in self._stable[:n_ok_prefix])
        d = n_ok_prefix
        while d > 0 and rets[d - 1] >= min_ret_rest:
            d -= 1
        d = min(d, max_bars)
        d -= d % K
        if d <= 0:
            return 0, 0, 0
        # The dropped rows' rets must be exactly ranks [0, d): every
        # retained ret larger than all dropped rets.  After trimming d
        # to ret-order (rets is sorted; rows aren't), re-check that the
        # first d rows *by ret* are a row prefix too — for register
        # workloads rows are emitted completion-ordered so this holds;
        # bail (discard nothing) when it doesn't rather than risk a
        # rank permutation.
        cut = rets[d - 1]
        prefix = self._stable[:d]
        if any(r[1] > cut for r in prefix) or any(
            r[1] <= cut for r in self._stable[d:n_ok_prefix]
        ):
            return 0, 0, 0
        # Event renumbering: shift so the first retained row lands at
        # event 0 (or keep the counter dense when nothing is retained).
        rest = self._stable[d:]
        candidates = [r[0] for r in rest] + [r[0] for r in self._rows]
        candidates += [inv_e for inv_e, _ in self._pending.values()]
        e_shift = min(candidates) if candidates else self._e
        self._stable = [
            (
                r[0] - e_shift,
                r[1] - e_shift if r[1] != NO_RET else NO_RET,
                r[2], r[3], r[4], r[5], r[6], r[7],
            )
            for r in rest
        ]
        self._rows = [
            (
                r[0] - e_shift,
                r[1] - e_shift if r[1] != NO_RET else NO_RET,
                r[2], r[3], r[4], r[5], r[6], r[7],
            )
            for r in self._rows
        ]
        self._pending = {
            p: (inv_e - e_shift, op)
            for p, (inv_e, op) in self._pending.items()
        }
        self._e -= e_shift
        # The ingest flush watermark tracks the (renumbered) counter.
        self._counted = max(0, self._counted - e_shift)
        return d, d, e_shift

    def _flush_ingest(self) -> None:
        """Publishes the client events consumed since the last flush
        (keeps `append` itself telemetry-free — the hot path's cost
        contract)."""
        if not telemetry.enabled():
            return
        d = self._e - self._counted
        if d > 0:
            telemetry.count("ingest.append.ops", d)
        self._counted = self._e

    def snapshot(self) -> tuple["PackedOps", int]:
        """(stable-prefix PackedOps, s).  The pack covers exactly the
        rows with inv < s and is WITNESS-ONLY: preds/horizon are left
        zero (the witness event walk never reads them; a full pack
        comes from finish())."""
        self._flush_ingest()
        with telemetry.span("ingest.snapshot", rows=self.n_rows):
            telemetry.count("ingest.snapshots")
            s = self.stable_bound()
            self._advance_stable(s)
            return _rows_to_packed(self._stable, with_preds=False), s

    def finish(self) -> "PackedOps":
        """Closes the builder: unfinished invocations become
        indeterminate, rows sort by invocation, preds/horizon are
        computed — byte-identical to pack_history on the same ops."""
        if self._finished:
            raise RuntimeError("PackedBuilder already finished")
        self._finished = True
        self._flush_ingest()
        with telemetry.span("ingest.finish", rows=self.n_rows):
            return self._close()

    def _close(self) -> "PackedOps":
        """finish() without its telemetry (pack_history's per-op path)."""
        # Unfinished invocations are indeterminate, in pending dict
        # order.
        for inv_e, inv_op in self._pending.values():
            self._emit(inv_e, inv_op, -1, None)
        self._pending.clear()
        rows = self._stable + self._rows
        rows.sort(key=lambda r: r[0])
        return _rows_to_packed(rows, with_preds=True)


def _require_i32(arr: "np.ndarray") -> None:
    """The process/status/f/a0/a1 columns narrow to int32 on device;
    a0/a1 carry model-encoded op arguments, which nothing bounds.  A
    value past int32 would wrap silently in the cast below and corrupt
    every verdict downstream, so bail loudly first (the
    wgl_witness._plan_blocks idiom)."""
    if not arr.size:
        return
    cols = arr[:, 2:7]
    lo = int(cols.min())
    hi = int(cols.max())
    if lo < -(2 ** 31) or hi >= 2 ** 31:
        raise OverflowError(
            f"packed op column value out of int32 range "
            f"[{lo}, {hi}]: re-encode op arguments (a0/a1) into a "
            f"dense int32 domain before packing"
        )


def _rows_to_packed(rows: list, *, with_preds: bool) -> "PackedOps":
    """The builder's inv-sorted row tuples -> PackedOps."""
    if rows:
        arr = np.array(rows, dtype=np.int64)
    else:
        arr = np.zeros((0, 8), dtype=np.int64)
    return _packed(arr, with_preds=with_preds)


def _packed(arr: "np.ndarray", *, with_preds: bool) -> "PackedOps":
    """Inv-sorted (n, 8) int64 rows (inv, ret, process, status, f, a0,
    a1, src_index) -> PackedOps.  with_preds=False leaves preds/horizon
    zero for witness-only snapshots."""
    inv = arr[:, 0]
    ret = arr[:, 1]
    n = arr.shape[0]
    _require_i32(arr)

    if with_preds:
        # preds[a] = #{y != a : ret(y) < inv(a)}
        # horizon[a] = #{y != a : inv(y) < ret(a)}
        # O(n log n) via sorted ret values.
        ret_sorted = np.sort(ret)
        preds = np.searchsorted(ret_sorted, inv, side="left").astype(np.int64)
        # inv is sorted ascending already; count invs strictly below
        # each ret.  Subtract self when inv(a) < ret(a) (always true
        # for completed ops; for NO_RET ops every other op counts, self
        # too — subtract 1).
        inv_before_ret = np.searchsorted(inv, ret, side="left").astype(np.int64)
        horizon = inv_before_ret - 1
        horizon = np.minimum(horizon, n - 1)
    else:
        preds = np.zeros(n, dtype=np.int64)
        horizon = np.zeros(n, dtype=np.int64)

    return PackedOps(
        inv=inv.astype(np.int64),
        ret=ret,
        process=arr[:, 2].astype(np.int32),
        status=arr[:, 3].astype(np.int32),
        f=arr[:, 4].astype(np.int32),
        a0=arr[:, 5].astype(np.int32),
        a1=arr[:, 6].astype(np.int32),
        src_index=arr[:, 7].astype(np.int64),
        preds=preds,
        horizon=horizon,
    )


# -- the columnar pass --------------------------------------------------------

#: Type codes of the columnar pass.  A completion that is neither :ok
#: nor :fail reads as :info, as the per-op path reads it.
_INVOKE_C, _OK_C, _FAIL_C, _INFO_C = 0, 1, 2, 3
_TYPE_CODES = {INVOKE: _INVOKE_C, OK: _OK_C, FAIL: _FAIL_C}

_process_of = attrgetter("process")
_index_of = attrgetter("index")


def _client_columns(ops: list) -> tuple["np.ndarray", "np.ndarray"]:
    """(type codes int8, processes int64) of a list of client ops."""
    codes = _TYPE_CODES
    types = np.array([codes.get(o.type, _INFO_C) for o in ops],
                     dtype=np.int8)
    procs = np.fromiter(map(_process_of, ops), dtype=np.int64,
                        count=len(ops))
    return types, procs


def _pair(types: "np.ndarray", procs: "np.ndarray"):
    """Pairs invocations with completions over a run of client ops,
    columnar, exactly as `PackedBuilder._append_client` does op by op.

    After any op of process p, p's pending state is just "that op was
    an invocation".  So along each process's events a completion pairs
    with its predecessor iff that is an invocation, and an invocation
    is superseded (indeterminate) iff its successor is one too.  A
    stable sort by process lays each process's events side by side and
    makes both relations shifted masks.

    Returns `(inv, comp, tail, tail_start)`, positions into the run.
    `inv`/`comp` are the rows in the order the per-op path emits them
    (a paired row at its completion, a superseded invocation at the one
    that supersedes it), `comp` -1 for a row without completion, rows
    completed :fail dropped.  `tail` are the invocations left pending,
    one per process, in the order they entered the pending dict:
    `tail_start`, the first invocation of the process's trailing run.
    """
    m = types.shape[0]
    key = procs
    if m and -(2 ** 15) <= procs.min() and procs.max() < 2 ** 15:
        # A stable sort of int16 is a radix sort, several times faster.
        # jepsenlint: ignore[device.unguarded-narrowing] -- range checked above
        key = procs.astype(np.int16)
    order = np.argsort(key, kind="stable")
    is_inv = types[order] == _INVOKE_C
    ps = procs[order]
    same = ps[1:] == ps[:-1]            # sorted j + 1 shares j's process
    after_inv = np.zeros(m, dtype=bool)
    after_inv[1:] = same & is_inv[:-1]
    before_inv = np.zeros(m, dtype=bool)
    before_inv[:-1] = same & is_inv[1:]
    last = np.ones(m, dtype=bool)
    last[:-1] = ~same
    # Each row under the event that emits it; no event emits two.
    by_emit = np.full(m, -1, dtype=np.int64)
    j = np.flatnonzero(after_inv & ~is_inv)
    by_emit[order[j]] = order[j - 1]
    j = np.flatnonzero(before_inv & is_inv)
    by_emit[order[j + 1]] = order[j]
    at = np.flatnonzero(by_emit >= 0)
    t = types[at]
    live = t != _FAIL_C                 # :fail — certainly never happened
    at = at[live]
    inv = by_emit[at]
    comp = np.where(t[live] == _INVOKE_C, -1, at)
    # Each process's trailing run of invocations entered the pending
    # dict at its first and stays there, its last as the value.
    starts = np.where(is_inv & ~after_inv, np.arange(m), 0)
    np.maximum.accumulate(starts, out=starts)
    j = np.flatnonzero(is_inv & last)
    tail_start = order[starts[j]]
    by = np.argsort(tail_start, kind="stable")
    return inv, comp, order[j][by], tail_start[by]


def _encode_rows(encode: OpEncoderFn, ops: list, types: "np.ndarray",
                 procs: "np.ndarray", events: "np.ndarray",
                 inv: "np.ndarray", comp: "np.ndarray") -> "np.ndarray":
    """Encodes the rows `_pair` found in the order given, the per-op
    path's emit order: the model's interner assigns codes in the order
    it first sees a value, so that order fixes a0/a1.  One call to the
    encoder's batched form `encode.many` where it has one, else one
    `encode` call per row.  Returns the rows the encoder keeps, in the
    same order, as (r, 8) int64 rows like PackedBuilder's; `events`
    maps a position in `ops` to its event index."""
    invs = [ops[i] for i in inv.tolist()]
    comps = [ops[c] if c >= 0 else None for c in comp.tolist()]
    many = getattr(encode, "many", None)
    if many is not None:
        res = many(zip(invs, comps))
    else:
        res = list(map(encode, invs, comps))
    kept = [r for r in res if r is not None]
    codes = np.fromiter(chain.from_iterable(kept), dtype=np.int64)
    if codes.shape[0] != 3 * len(kept):
        raise ValueError("an op encoder returns (f, a0, a1) or None")
    src = np.fromiter(map(_index_of, invs), dtype=np.int64,
                      count=len(invs))
    if len(kept) < len(res):
        keep = np.fromiter((r is not None for r in res), dtype=bool,
                           count=len(res))
        inv, comp, src = inv[keep], comp[keep], src[keep]
    ok = comp >= 0
    ok[ok] = types[comp[ok]] == _OK_C
    arr = np.empty((len(kept), 8), dtype=np.int64)
    arr[:, 0] = events[inv]
    arr[:, 1] = np.where(ok, events[comp], NO_RET)
    arr[:, 2] = procs[inv]
    arr[:, 3] = np.where(ok, ST_OK, ST_INFO)
    arr[:, 4:7] = codes.reshape(-1, 3)
    arr[:, 7] = src
    return arr


#: Below this many client events pack_history takes the per-op path:
#: the columnar pass's fixed numpy cost outweighs what it saves (a
#: jepsen.independent key is a few hundred events).
_PACK_MIN = 448


def pack_history(h: History, encode: OpEncoderFn) -> PackedOps:
    """Packs the client portion of a history into columnar arrays.

    Pipeline (mirrors knossos's preprocessing as observed through the
    checker API, checker.clj:214-233):
      1. keep client ops only;
      2. pair invocations with completions;
      3. drop certain failures (:fail) — they never happened;
      4. ops whose completion is missing or :info become indeterminate
         (ret = NO_RET);
      5. encode (f, value) via the model's encoder; encoders may drop
         no-effect indeterminate ops (e.g. :info reads).

    From `_PACK_MIN` client events up, one columnar pass (`_pair`,
    `_encode_rows`) over the whole history; below, the builder's per-op
    path.  The per-op reference is `PackedBuilder.append` + `finish`:
    the columnar pass returns the same bytes (`packed_to_bytes`) on the
    same ops and a fresh encoder, rows reaching the encoder in the same
    order — at their completion, at the invocation that supersedes
    them, and the unfinished last, in the order their processes entered
    the pending dict.
    """
    client = [o for o in h if isinstance(o.process, int)]
    if len(client) < _PACK_MIN:
        telemetry.count("ingest.pack.scalar")
        b = PackedBuilder(encode)
        for o in client:
            b._append_client(o)
        packed = b._close()
        telemetry.count("ingest.pack.rows", packed.n)
        return packed
    types, procs = _client_columns(client)
    inv, comp, tail, _ = _pair(types, procs)
    inv = np.concatenate([inv, tail])
    comp = np.concatenate([comp, np.full(tail.shape[0], -1, dtype=np.int64)])
    if not hasattr(encode, "many"):
        telemetry.count("ingest.pack.scalar")
    arr = _encode_rows(encode, client, types, procs,
                       np.arange(len(client), dtype=np.int64), inv, comp)
    telemetry.count("ingest.pack.rows", arr.shape[0])
    return _packed(arr[np.argsort(arr[:, 0])], with_preds=True)
