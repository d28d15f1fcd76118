"""Collection models: set, unordered-queue, FIFO queue.

Host-only knossos.model equivalents (SURVEY.md §2.4).  These back the
generic `linearizable` checker for collection workloads; the cheap
specialized checkers (checker.set / checker.queue / checker.total_queue)
don't need a model at all, mirroring the reference split
(checker.clj:235-287, 648-708).

These models carry unbounded Python collections.  UnorderedQueue and
FIFOQueue have bounded packed int32 forms (capacity-gated, see the
UnorderedQueue docstring); SetModel has none — `packed()` raises and
the linearizable checker falls back to the host-model search.
"""

from __future__ import annotations

from typing import Any, FrozenSet, Tuple

from ..history.core import Op
from .base import Model, inconsistent


def _freeze(v: Any) -> Any:
    if isinstance(v, list):
        return tuple(v)
    if isinstance(v, set):
        return frozenset(v)
    return v


class SetModel(Model):
    """A grow-only set: `add` elements, `read` the full contents."""

    __slots__ = ("items",)

    def __init__(self, items: FrozenSet[Any] = frozenset()):
        self.items = frozenset(items)

    def step(self, op: Op):
        if op.f == "add":
            return SetModel(self.items | {_freeze(op.value)})
        if op.f == "read":
            if op.value is None:
                return self
            got = frozenset(_freeze(x) for x in op.value)
            if got == self.items:
                return self
            return inconsistent(
                f"read {sorted(map(repr, got))} but set contained "
                f"{sorted(map(repr, self.items))}"
            )
        return inconsistent(f"unknown op f {op.f!r}")

    def __eq__(self, other):
        return type(other) is SetModel and other.items == self.items

    def __hash__(self):
        return hash(("SetModel", self.items))

    def __repr__(self):
        return f"SetModel({sorted(map(repr, self.items))})"


class UnorderedQueue(Model):
    """A queue where dequeue may return any enqueued-but-not-dequeued
    element (knossos.model/unordered-queue).

    Device form: a bounded multiset of `packed_capacity` int32 slots
    (0 = empty), kept sorted for canonical equality.  The packed form
    is exact only when the history can never hold more than
    capacity elements; `validate_packed` checks a sound upper bound
    (enqueues invoked so far minus dequeues completed so far, maxed
    over the walk) and the checker falls back to the host model when
    it could bind.  Indeterminate dequeues with unknown values have no
    deterministic packed transition, so packing such histories raises
    and likewise falls back."""

    __slots__ = ("pending", "_packed_cache")
    packed_capacity = 32

    def __init__(self, pending: Tuple[Any, ...] = ()):
        self.pending = tuple(pending)

    def step(self, op: Op):
        v = _freeze(op.value)
        if op.f == "enqueue":
            return UnorderedQueue(self.pending + (v,))
        if op.f == "dequeue":
            if v in self.pending:
                i = self.pending.index(v)
                return UnorderedQueue(self.pending[:i] + self.pending[i + 1 :])
            return inconsistent(f"can't dequeue {v!r}: not in queue")
        return inconsistent(f"unknown op f {op.f!r}")

    def __eq__(self, other):
        return type(other) is UnorderedQueue and sorted(
            map(repr, other.pending)
        ) == sorted(map(repr, self.pending))

    def __hash__(self):
        return hash(("UnorderedQueue", tuple(sorted(map(repr, self.pending)))))

    def __repr__(self):
        return f"UnorderedQueue({list(self.pending)!r})"

    def _compile_packed(self):
        return _queue_packed(self.pending, self.packed_capacity, fifo=False)


def _queue_packed(initial, capacity: int, *, fifo: bool):
    """Shared packed form for the bounded queues: `capacity` int32
    slots, 0 = empty.  Unordered keeps the multiset sorted for
    canonical equality; FIFO keeps insertion order left-aligned.  See
    UnorderedQueue's docstring for the soundness gates."""
    from ..history.core import OK
    from ..history.packed import NIL, Interner
    from .base import PackedModel, intern_value

    C = capacity
    initial = tuple(initial)
    if len(initial) > C:
        raise NotImplementedError("initial queue exceeds capacity")
    interner = Interner()
    interner.intern(None)  # reserve id 0 -> code 1 for None
    F_ENQ, F_DEQ = 0, 1

    def code(v):
        return intern_value(interner, _freeze(v)) + 1  # 0 = empty

    def encode(inv, comp):
        if inv.f == "enqueue":
            return (F_ENQ, code(inv.value), NIL)
        if inv.f == "dequeue":
            if comp is None or comp.type != OK:
                raise ValueError(
                    "indeterminate dequeue has no packed form"
                )
            return (F_DEQ, code(comp.value), NIL)
        raise ValueError(f"queue model can't encode f {inv.f!r}")

    codes = [code(x) for x in initial]
    if fifo:
        init_state = tuple(codes + [0] * (C - len(codes)))
    else:
        init_state = tuple([0] * (C - len(codes)) + sorted(codes))

    def py_step(state, f, a0, a1):
        s = list(state)
        if fifo:
            if f == F_ENQ:
                if 0 not in s:
                    return state, False
                s[s.index(0)] = a0
                return tuple(s), True
            if s[0] != a0 or a0 == 0:
                return state, False
            return tuple(s[1:] + [0]), True
        if f == F_ENQ:
            if 0 not in s:
                return state, False
            s[s.index(0)] = a0
            return tuple(sorted(s)), True
        if a0 not in s:
            return state, False
        s.remove(a0)
        return tuple(sorted([0] + s)), True

    def jax_step(state, f, a0, a1):
        import jax.numpy as jnp

        is_enq = f == F_ENQ
        if fifo:
            # Left-aligned: first zero is the tail slot.
            length = (state != 0).sum()
            has_room = length < C
            enq = state.at[jnp.clip(length, 0, C - 1)].set(a0)
            head_ok = (state[0] == a0) & (a0 != 0)
            deq = jnp.roll(state, -1).at[C - 1].set(0)
            legal = jnp.where(is_enq, has_room, head_ok)
            new = jnp.where(
                is_enq,
                jnp.where(has_room, enq, state),
                jnp.where(head_ok, deq, state),
            )
            return new, legal
        has_room = (state == 0).any()
        enq = state.at[jnp.argmin(state)].set(a0)
        eq = state == a0
        present = eq.any()
        deq = jnp.where(
            jnp.arange(state.shape[0]) == jnp.argmax(eq), 0, state
        )
        legal = jnp.where(is_enq, has_room, present)
        new = jnp.where(is_enq, enq, jnp.where(present, deq, state))
        return jnp.sort(new), legal

    def jax_step_rows(states, f, a0, a1):
        # Scatter-free lane-major FIFO step for the Pallas sweep
        # (states is (C, B), left-aligned): the enqueue slot is picked
        # by a row-iota mask, dequeue is a static one-row shift.
        import jax
        import jax.numpy as jnp

        is_enq = f == F_ENQ
        nonzero = (states != 0).astype(jnp.int32)
        length = nonzero.sum(axis=0)                      # (B,)
        has_room = (length < C).astype(jnp.int32)
        row = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
        slot = row == length[None, :]                     # (C, B)
        # length == C matches no row, so a full lane keeps its state.
        enq = jnp.where(slot, a0, states)
        head_ok = ((states[0] == a0) & (a0 != 0)).astype(jnp.int32)
        deq = jnp.concatenate(
            [states[1:], jnp.zeros((1, states.shape[1]), jnp.int32)],
            axis=0,
        )
        legal = jnp.where(is_enq, has_room, head_ok)
        new = jnp.where(
            is_enq, enq,
            jnp.where((head_ok != 0)[None, :], deq, states),
        )
        return new, legal

    def jax_step_rows_unordered(states, f, a0, a1):
        # Sort-free lane-major multiset step: enqueue fills the first
        # zero row, dequeue clears the first row matching a0 — both
        # picked as the least masked row index (a min-reduction; Mosaic
        # lowers neither cumsum nor argmin/argmax gathers).  The resulting state is NOT kept sorted; that is
        # sound because enqueue/dequeue legality is order-independent
        # and canonical (sorted) form is only needed for the heavy
        # rounds' state dedup — whose inputs are jax_step outputs,
        # which re-sort unconditionally.  Unsorted states therefore
        # only pass through the sweep, never reach a dedup compare.
        import jax
        import jax.numpy as jnp

        is_enq = f == F_ENQ
        row = jax.lax.broadcasted_iota(jnp.int32, states.shape, 0)
        zero_i = (states == 0).astype(jnp.int32)
        first_zero = row == jnp.where(states == 0, row, C).min(axis=0)
        has_room = zero_i.max(axis=0)                     # (B,) 0/1
        enq = jnp.where(first_zero, a0, states)
        match_i = (states == a0).astype(jnp.int32)
        first_match = row == jnp.where(states == a0, row, C).min(axis=0)
        present = match_i.max(axis=0)                     # (B,) 0/1
        deq = jnp.where(first_match, 0, states)
        legal = jnp.where(is_enq, has_room, present)
        new = jnp.where(
            is_enq, enq,
            jnp.where((present != 0)[None, :], deq, states),
        )
        return new, legal

    def validate_packed(packed) -> "str | None":
        # Sound size bound at any linearization point t: every enqueue
        # invoked by t could be in the queue; dequeues completed by t
        # must already be linearized (removed).
        size = len(initial)
        worst = size
        events = []  # (when, +1 enq-invoked / -1 deq-completed)
        for i in range(packed.n):
            if packed.f[i] == F_ENQ:
                events.append((int(packed.inv[i]), 1))
            else:
                events.append((int(packed.ret[i]), -1))
        for _, delta in sorted(events):
            size += delta
            worst = max(worst, size)
        if worst > C:
            return (
                f"history may hold {worst} elements; packed "
                f"capacity is {C}"
            )
        return None

    def describe_op(f, a0, a1):
        v = interner.value(a0 - 1) if a0 > 0 else "?"
        return ("enqueue " if f == F_ENQ else "dequeue -> ") + repr(v)

    return PackedModel(
        name="fifo-queue" if fifo else "unordered-queue",
        state_width=C,
        init_state=init_state,
        encode=encode,
        py_step=py_step,
        jax_step=jax_step,
        interner=interner,
        describe_op=describe_op,
        validate_packed=validate_packed,
        jax_step_rows=(jax_step_rows if fifo
                       else jax_step_rows_unordered),
    )


class FIFOQueue(Model):
    """A strict FIFO queue: dequeue must return the head.  Device form:
    left-aligned bounded slots with the same capacity/indeterminate
    gates as UnorderedQueue."""

    __slots__ = ("items", "_packed_cache")
    packed_capacity = 32

    def __init__(self, items: Tuple[Any, ...] = ()):
        self.items = tuple(items)

    def _compile_packed(self):
        return _queue_packed(self.items, self.packed_capacity, fifo=True)

    def step(self, op: Op):
        v = _freeze(op.value)
        if op.f == "enqueue":
            return FIFOQueue(self.items + (v,))
        if op.f == "dequeue":
            if not self.items:
                return inconsistent(f"can't dequeue {v!r} from empty queue")
            if self.items[0] == v:
                return FIFOQueue(self.items[1:])
            return inconsistent(
                f"dequeued {v!r} but head was {self.items[0]!r}"
            )
        return inconsistent(f"unknown op f {op.f!r}")

    def __eq__(self, other):
        return type(other) is FIFOQueue and other.items == self.items

    def __hash__(self):
        return hash(("FIFOQueue", self.items))

    def __repr__(self):
        return f"FIFOQueue({list(self.items)!r})"


def set_model() -> SetModel:
    return SetModel()


def unordered_queue() -> UnorderedQueue:
    return UnorderedQueue()


def fifo_queue() -> FIFOQueue:
    return FIFOQueue()
