"""Register-family models: register, cas-register, multi-register.

Semantics mirror knossos.model's registers as used by the reference
(`knossos.model/cas-register` at tests/linearizable_register.clj:22-53;
protocol in doc/tutorial/04-checker.md): a read of `nil` is unconstrained
(unknown return), reads must otherwise match the current value, writes
always succeed, cas succeeds iff the old value matches.
"""

from __future__ import annotations

from typing import Any, Optional

from ..history.core import OK, Op
from ..history.packed import NIL, Interner
from .base import Inconsistent, Model, PackedModel, inconsistent, intern_value

F_READ, F_WRITE, F_CAS = 0, 1, 2
_F_NAMES = {F_READ: "read", F_WRITE: "write", F_CAS: "cas"}


class Register(Model):
    """A single read/write register."""

    __slots__ = ("value", "_packed_cache")
    fs = ("read", "write")

    def __init__(self, value: Any = None):
        self.value = value

    def step(self, op: Op):
        if op.f == "read":
            if op.value is None or op.value == self.value:
                return self
            return inconsistent(
                f"read {op.value!r} but register held {self.value!r}"
            )
        if op.f == "write":
            return type(self)(op.value)
        return inconsistent(f"unknown op f {op.f!r}")

    def __eq__(self, other):
        return type(other) is type(self) and other.value == self.value

    def __hash__(self):
        return hash((type(self).__name__, self.value))

    def __repr__(self):
        return f"{type(self).__name__}({self.value!r})"

    # -- packed -----------------------------------------------------------

    def _compile_packed(self) -> PackedModel:
        return _register_packed(self, allow_cas=False)


class CASRegister(Register):
    """A register with read/write/compare-and-set — the canonical
    linearizability workload (BASELINE.json configs 1 and 4)."""

    fs = ("read", "write", "cas")

    def step(self, op: Op):
        if op.f == "cas":
            old, new = op.value
            if self.value == old:
                return CASRegister(new)
            return inconsistent(
                f"cas from {old!r} but register held {self.value!r}"
            )
        return super().step(op)

    def _compile_packed(self) -> PackedModel:
        return _register_packed(self, allow_cas=True)


def _register_packed(model: Register, allow_cas: bool) -> PackedModel:
    interner = Interner()
    nil_code = interner.intern(None)  # id 0
    init = (intern_value(interner, model.value),)

    def encode(inv: Op, comp: Optional[Op]):
        f = inv.f
        if f == "read":
            if comp is None or comp.type != OK:
                return None  # indeterminate read: no effect, droppable
            if comp.value is None:
                return None  # unknown return: unconstrained, droppable
            return (F_READ, intern_value(interner, comp.value), NIL)
        if f == "write":
            return (F_WRITE, intern_value(interner, inv.value), NIL)
        if f == "cas" and allow_cas:
            old, new = inv.value
            return (
                F_CAS,
                intern_value(interner, old),
                intern_value(interner, new),
            )
        raise ValueError(f"register model can't encode op f {f!r}")

    def encode_many(items):
        # Columnar-ingest hook (pack_history, PackedBuilder.append_many):
        # encode() over an iterable of (inv, comp) pairs, in order, with
        # the interner inlined — one loop, no per-op intern_value/intern
        # call frames.  MUST stay semantically in lockstep with encode():
        # same interner dicts, same drops, same codes, so the packed
        # bytes are identical.
        ids = interner._ids
        vals = interner.values
        out = []
        add = out.append
        for inv, comp in items:
            f = inv.f
            if f == "read":
                if comp is None or comp.type != OK:
                    add(None)
                    continue
                v = comp.value
                if v is None:
                    add(None)
                    continue
            elif f == "write":
                v = inv.value
            elif f == "cas" and allow_cas:
                old, new = inv.value
                if isinstance(old, list):
                    old = tuple(old)
                if isinstance(new, list):
                    new = tuple(new)
                i0 = ids.get(old)
                if i0 is None:
                    i0 = len(vals)
                    ids[old] = i0
                    vals.append(old)
                i1 = ids.get(new)
                if i1 is None:
                    i1 = len(vals)
                    ids[new] = i1
                    vals.append(new)
                add((F_CAS, i0, i1))
                continue
            else:
                raise ValueError(
                    f"register model can't encode op f {f!r}"
                )
            if isinstance(v, list):
                v = tuple(v)
            i = ids.get(v)
            if i is None:
                i = len(vals)
                ids[v] = i
                vals.append(v)
            add((F_READ if f == "read" else F_WRITE, i, NIL))
        return out

    encode.many = encode_many

    def py_step(state, f, a0, a1):
        s = state[0]
        if f == F_READ:
            return state, s == a0
        if f == F_WRITE:
            return (a0,), True
        # cas
        return (a1,), s == a0

    def jax_step(state, f, a0, a1):
        import jax.numpy as jnp

        s = state[0]
        is_write = f == F_WRITE
        is_cas = f == F_CAS
        legal = is_write | (s == a0)
        new = jnp.where(is_write, a0, jnp.where(is_cas, a1, s))
        return state.at[0].set(new), legal

    def jax_step_rows(states, f, a0, a1):
        # Scatter-free lane-major form for the Pallas sweep (states
        # is (1, B); the single row IS the register).
        import jax.numpy as jnp

        s = states[0]
        is_write = f == F_WRITE
        is_cas = f == F_CAS
        legal = is_write | (s == a0)
        new = jnp.where(is_write, a0, jnp.where(is_cas, a1, s))
        return new[None, :], legal

    def describe_op(f: int, a0: int, a1: int) -> str:
        if f == F_READ:
            return f"read -> {interner.value(a0)!r}"
        if f == F_WRITE:
            return f"write {interner.value(a0)!r}"
        return f"cas {interner.value(a0)!r} -> {interner.value(a1)!r}"

    def refute_view(packed):
        import numpy as np

        from ..checker.refute import RefuteView
        from ..history.packed import NIL as _NIL

        f = packed.f
        return RefuteView(
            key=np.zeros(packed.n, dtype=np.int32),
            # reads assert the returned value; ok cas asserts the
            # expected old value at its linearization point
            asserts=np.where(f == F_READ, packed.a0,
                             np.where(f == F_CAS, packed.a0, _NIL)),
            # writes force their value; an :ok cas's new value is a
            # forced effect (it returned success)
            produces=np.where(f == F_WRITE, packed.a0,
                              np.where(f == F_CAS, packed.a1, _NIL)),
            init=np.array(init, dtype=np.int32),
        )

    return PackedModel(
        name="cas-register" if allow_cas else "register",
        state_width=1,
        init_state=init,
        encode=encode,
        py_step=py_step,
        jax_step=jax_step,
        interner=interner,
        describe_op=describe_op,
        jax_step_rows=jax_step_rows,
        refute_view=refute_view,
    )


class MultiRegister(Model):
    """A fixed set of named registers; ops read/write a single (k, v) pair
    (knossos.model/multi-register restricted to unit txns — the
    per-key-WGL benchmark config in BASELINE.json uses
    jepsen.independent to shard keys instead of packing them here)."""

    __slots__ = ("values", "_packed_cache")

    def __init__(self, values: dict[Any, Any]):
        self.values = dict(values)

    def step(self, op: Op):
        k, v = op.value
        if k not in self.values:
            return inconsistent(f"no such register {k!r}")
        if op.f == "read":
            if v is None or self.values[k] == v:
                return self
            return inconsistent(
                f"read {v!r} from {k!r} which held {self.values[k]!r}"
            )
        if op.f == "write":
            nv = dict(self.values)
            nv[k] = v
            return MultiRegister(nv)
        return inconsistent(f"unknown op f {op.f!r}")

    def __eq__(self, other):
        return type(other) is MultiRegister and other.values == self.values

    def __hash__(self):
        return hash(tuple(sorted(self.values.items(), key=repr)))

    def __repr__(self):
        return f"MultiRegister({self.values!r})"

    def _compile_packed(self) -> PackedModel:
        interner = Interner()
        interner.intern(None)
        keys = list(self.values.keys())
        key_idx = {k: i for i, k in enumerate(keys)}
        init = tuple(intern_value(interner, self.values[k]) for k in keys)

        def encode(inv: Op, comp: Optional[Op]):
            if inv.f == "read":
                if comp is None or comp.type != OK:
                    return None
                k, v = comp.value
                if v is None:
                    return None
                return (F_READ, key_idx[k], intern_value(interner, v))
            if inv.f == "write":
                k, v = inv.value
                return (F_WRITE, key_idx[k], intern_value(interner, v))
            raise ValueError(f"multi-register can't encode op f {inv.f!r}")

        def py_step(state, f, a0, a1):
            if f == F_READ:
                return state, state[a0] == a1
            s = list(state)
            s[a0] = a1
            return tuple(s), True

        def jax_step(state, f, a0, a1):
            import jax.numpy as jnp

            cur = state[a0]
            is_write = f == F_WRITE
            legal = is_write | (cur == a1)
            new = jnp.where(is_write, a1, cur)
            return state.at[a0].set(new), legal

        def jax_step_rows(states, f, a0, a1):
            # Scatter-free lane-major form for the Pallas sweep
            # (states is (n_keys, B)): the written key row is selected
            # by mask, not scatter.
            import jax
            import jax.numpy as jnp

            nk = states.shape[0]
            key_mask = (
                jax.lax.broadcasted_iota(jnp.int32, (nk, 1), 0) == a0
            )
            cur = jnp.where(key_mask, states, 0).sum(axis=0)  # (B,)
            is_write = f == F_WRITE
            legal = is_write | (cur == a1)
            out = jnp.where(key_mask & is_write, a1, states)
            return out, legal

        def describe_op(f: int, a0: int, a1: int) -> str:
            verb = "read" if f == F_READ else "write"
            return f"{verb} {keys[a0]!r} {interner.value(a1)!r}"

        def refute_view(packed):
            import numpy as np

            from ..checker.refute import RefuteView
            from ..history.packed import NIL as _NIL

            f = packed.f
            return RefuteView(
                key=packed.a0.astype(np.int32),
                asserts=np.where(f == F_READ, packed.a1, _NIL),
                produces=np.where(f == F_WRITE, packed.a1, _NIL),
                init=np.array(init, dtype=np.int32),
            )

        return PackedModel(
            name="multi-register",
            state_width=len(keys),
            init_state=init,
            encode=encode,
            py_step=py_step,
            jax_step=jax_step,
            interner=interner,
            describe_op=describe_op,
            jax_step_rows=jax_step_rows,
            refute_view=refute_view,
        )


def register(value: Any = None) -> Register:
    return Register(value)


def cas_register(value: Any = None) -> CASRegister:
    return CASRegister(value)


def multi_register(values: dict[Any, Any]) -> MultiRegister:
    return MultiRegister(values)
