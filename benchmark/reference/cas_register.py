"""The plain reference for the cas-register family: linearizability of one
register's history, decided without the program.

A history is a list of events `(type, f, value, process)` in real-time
order, `type` one of invoke / ok / fail / info, `f` one of read / write /
cas; a cas carries `(old, new)`.  The register starts at None.  As in
knossos: a `:fail` op never happened; an `:info` op may have happened at
any instant after its invocation, or never; an invocation with no
completion is `:info`.

`decide` answers True (linearizable), False, or "unknown" (the search
ran out of budget) in three plain steps:

1. a certificate: the generator's linearization order, checked op by op
   against the sequential register and against real time;
2. an unsupported read: a completed read of a value that no op in the
   history writes, and that is not the initial value, has no
   linearization;
3. failing both, Wing and Gong's search with Lowe's memo of
   (linearized set, state), depth first, ops that complete earliest
   tried first.

`info_as_fail` is the control: it breaks the `:info` guarantee by
treating every indeterminate op as failed.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

ILLEGAL = object()

#: Configurations the search may visit before it answers "unknown".
SEARCH_BUDGET = 200_000


@dataclass(frozen=True)
class Operation:
    id: int                 # index of its invocation event
    inv: int                # event position of the invocation
    comp: Optional[int]     # event position of the completion; None: open
    f: str
    value: object           # write: value; cas: (old, new); read: result


def operations(events: list, info_as_fail: bool = False) -> list:
    """Pairs invocations with completions by process; drops `:fail`
    ops (and `:info` ops under `info_as_fail`)."""
    open_: dict = {}
    ops = []
    for pos, (typ, f, value, p) in enumerate(events):
        if typ == "invoke":
            open_[p] = (pos, f, value)
            continue
        at, f0, v0 = open_.pop(p)
        if typ == "fail":
            continue
        if typ == "info":
            if not info_as_fail:
                ops.append(Operation(at, at, None, f0, v0))
            continue
        ops.append(Operation(at, at, pos, f0, value if f0 == "read" else v0))
    for at, f0, v0 in open_.values():
        if not info_as_fail:
            ops.append(Operation(at, at, None, f0, v0))
    ops.sort(key=lambda o: o.inv)
    return ops


def step(state, op: Operation):
    """The sequential register: the state after `op`, or ILLEGAL."""
    if op.f == "read":
        if op.comp is None or op.value == state:
            return state
        return ILLEGAL
    if op.f == "write":
        return op.value
    old, new = op.value
    return new if state == old else ILLEGAL


def check_certificate(ops: list, order: list) -> bool:
    """True when `order` (operation ids) linearizes `ops`: every op at
    most once, every completed op present, each at an instant inside its
    own interval and after the one before it, and each legal in turn."""
    by_id = {o.id: o for o in ops}
    state, t, seen = None, -1, set()
    for oid in order:
        op = by_id.get(oid)
        if op is None or oid in seen:
            return False
        seen.add(oid)
        t = max(t, op.inv)
        if op.comp is not None and not t < op.comp:
            return False
        state = step(state, op)
        if state is ILLEGAL:
            return False
    return all(o.comp is None or o.id in seen for o in ops)


def search(ops: list, budget: int = SEARCH_BUDGET):
    """Wing and Gong's search over (linearized set, state).  The set is
    kept as `lo`, below which every op is linearized, and a bit mask of
    the ops from `lo` on, so a long history costs its window, not its
    length."""
    n = len(ops)
    inf = float("inf")
    seen = set()
    stack = [(0, 0, None)]
    while stack:
        lo, rel, state = stack.pop()
        while rel & 1:
            rel >>= 1
            lo += 1
        if (lo, rel, state) in seen:
            continue
        seen.add((lo, rel, state))
        if len(seen) > budget:
            return "unknown"
        # Every op invoked before the earliest pending completion may
        # go next; no op invoked after it can complete before it.
        m, open_, i = inf, [], lo
        while i < n and ops[i].inv < m:
            if not rel >> (i - lo) & 1:
                open_.append(i)
                if ops[i].comp is not None:
                    m = min(m, ops[i].comp)
            i += 1
        if m == inf:
            return True     # every completed op is linearized
        cands = []
        for i in open_:
            if ops[i].inv < m:
                s2 = step(state, ops[i])
                if s2 is not ILLEGAL:
                    c = ops[i].comp
                    cands.append((inf if c is None else c, i, s2))
        # Depth first: the op that completes earliest is tried first.
        for _, i, s2 in sorted(cands, key=lambda c: c[:2], reverse=True):
            stack.append((lo, rel | 1 << (i - lo), s2))
    return False


def unsupported_read(ops: list) -> bool:
    """Some completed read returns a value no op writes."""
    written = {None}
    for o in ops:
        if o.f == "write":
            written.add(o.value)
        elif o.f == "cas":
            written.add(o.value[1])
    return any(o.f == "read" and o.comp is not None
               and o.value not in written for o in ops)


def decide(events: list, witness: list, info_as_fail: bool = False,
           budget: int = SEARCH_BUDGET):
    ops = operations(events, info_as_fail)
    if check_certificate(ops, witness):
        return True
    if unsupported_read(ops):
        return False
    return search(ops, budget)
