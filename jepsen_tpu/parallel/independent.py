"""Per-key independent checking — `jepsen.independent`, TPU-sharded.

The reference lifts single-key workloads to many keys: op values become
`(k, v)` tuples, the history is split into per-key subhistories, and each
key is checked independently under a bounded thread pool
(/root/reference/jepsen/src/jepsen/independent.clj:27, :259-325,
:327-377).  This module keeps the same host API but re-designs the
compute: when the base checker is a packed-model linearizability check,
all keys are packed into one padded batch and decided by a single
vmapped + shard_mapped device search (ops/wgl_batched.py) — per-key data
parallelism across the TPU mesh instead of a JVM thread pool.

Generator-side lifting (`sequential_generator`/`concurrent_generator`,
independent.clj:37-257) lives in jepsen_tpu.generator.independent, next
to the generator machinery it builds on.
"""

from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict
from typing import Any, NamedTuple, Optional

from .. import telemetry
from ..checker.core import Checker, check_safe, merge_valid
from ..checker.linearizable import Linearizable
from ..history.core import History, Op
from ..utils import bounded_pmap


class KV(NamedTuple):
    """A `[key value]` tuple op payload (independent.clj:18-35).  A
    distinct type — not a plain tuple — so multi-argument payloads like
    cas `(old, new)` aren't mistaken for keyed values."""

    key: Any
    value: Any

    def __repr__(self) -> str:
        return f"[{self.key!r} {self.value!r}]"


def kv(key: Any, value: Any) -> KV:
    return KV(key, value)


def is_kv(v: Any) -> bool:
    return isinstance(v, KV)


def tuple_gen(key: Any, value: Any) -> KV:
    """Alias mirroring `independent/tuple`."""
    return KV(key, value)


# ---------------------------------------------------------------------------
# Settle-verdict memoization
# ---------------------------------------------------------------------------

#: digest -> sanitized settle verdict.  Bounded LRU: planted-violation
#: and replayed-nemesis workloads repeat the SAME bad subhistory across
#: keys and across checks; each distinct one is decided once.
_SETTLE_MEMO_MAX = 2048
_settle_memo: "OrderedDict[str, dict]" = OrderedDict()
_settle_memo_lock = threading.Lock()

#: Result fields that cite positions in ONE key's slice of the full
#: history (src_index-based certificates, rendered artifacts).  A memo
#: entry is shared by textually identical subhistories at DIFFERENT
#: positions, so these never ride along.
_POSITIONAL_FIELDS = ("final-configs", "crashed-op", "counterexample-file")


def _settle_digest(p, pm) -> str:
    """Packed-history digest keying the settle memo.  Sound for verdict
    sharing because the packed check is purely code-level: the verdict
    is a function of the (inv, ret, status, f, a0, a1) columns, the
    model's step semantics (named), and its initial state — regardless
    of which concrete values the interner codes denote.  src_index is
    deliberately excluded: identical subhistories at different offsets
    in the full history must collide."""
    import numpy as np

    h = hashlib.sha256()
    h.update(
        f"{pm.name}|{tuple(int(v) for v in pm.init_state)}|"
        f"{pm.state_width}".encode()
    )
    for col in (p.inv, p.ret, p.status, p.f, p.a0, p.a1):
        h.update(np.ascontiguousarray(col).tobytes())
    return h.hexdigest()


def _online_digest(sess, pm, sub) -> Optional[str]:
    """Digest of one key's subhistory in the STREAMING SESSION's code
    space, for `sess.consume`.  Register encoders intern values in
    first-seen order, so a freshly compiled PackedModel assigns
    different codes than the session's (which interned in journal
    order) — the caller's own pack can never match the digest the
    session recorded.  Re-packing with the session's encoder INSTANCE
    reuses its interner, reproducing the exact byte stream the proof
    was recorded against.  Returns None (never consume) when the
    session checked a different model shape, or the re-pack fails."""
    spm = sess.pm
    if (spm.name != pm.name
            or tuple(int(v) for v in spm.init_state)
            != tuple(int(v) for v in pm.init_state)
            or spm.state_width != pm.state_width):
        return None
    try:
        from ..history.packed import pack_history

        return _settle_digest(pack_history(sub, spm.encode), spm)
    except Exception:  # noqa: BLE001 — fail closed to the post-hoc path
        return None


def _sanitize_settle(res: dict) -> dict:
    """A memo-shareable copy of a settle result: verdict and metadata,
    minus the positional certificate fields."""
    return {k: v for k, v in res.items() if k not in _POSITIONAL_FIELDS}


def _memo_get(digest: str) -> Optional[dict]:
    with _settle_memo_lock:
        r = _settle_memo.get(digest)
        if r is not None:
            _settle_memo.move_to_end(digest)
            return dict(r)
    return None


def clear_settle_memo() -> None:
    """Empties the cross-call settle memo.  Benchmarks and perf tests
    call this between reps so every rep measures the COLD settling
    ladder (screens + search), not a memo replay."""
    with _settle_memo_lock:
        _settle_memo.clear()


def invalidate_settle_memo(digest: str) -> None:
    """Evicts ONE digest's memoized verdict.  The streaming checker
    (jepsen_tpu/streaming/) memoizes a key's proof the moment the key
    goes quiet; when the key later takes more ops, that entry describes
    a mid-run prefix that no finished history will ever equal — it is
    dead weight at best and, if a recheck re-records the key, a stale
    twin of the live verdict.  Eviction is keyed so an online recheck
    drops exactly its own superseded entry instead of dumping every
    other run's cohort (which a full clear_settle_memo would)."""
    with _settle_memo_lock:
        _settle_memo.pop(digest, None)


def _memo_put(digest: str, res: dict) -> None:
    # Only decisive verdicts are worth remembering: an "unknown" is a
    # budget artifact of THIS call, and a later call with more budget
    # must not inherit it.
    if res.get("valid") not in (True, False):
        return
    with _settle_memo_lock:
        _settle_memo[digest] = _sanitize_settle(res)
        _settle_memo.move_to_end(digest)
        while len(_settle_memo) > _SETTLE_MEMO_MAX:
            _settle_memo.popitem(last=False)


def history_keys(h: History) -> list:
    """All keys in KV-valued ops, in first-seen order
    (independent.clj:259-269)."""
    seen: dict[Any, None] = {}
    for o in h:
        if is_kv(o.value):
            seen.setdefault(o.value.key, None)
    return list(seen)


def subhistories(h: History) -> dict[Any, History]:
    """Splits a history into per-key histories, unwrapping KV values
    (independent.clj:271-325).  Completions that lost their KV payload
    (e.g. an :info with value None) inherit the key of their process's
    pending invocation.  Ops keep their original indices, so per-key
    results can cite positions in the full history."""
    per_key: dict[Any, list[Op]] = {}
    pending: dict[Any, Any] = {}  # process -> key
    # Hot loop (every op of every test history passes through here):
    # one isinstance per op, one dict lookup per key, bound methods
    # hoisted — measured 2x over the straightforward form at 20k ops.
    pop = pending.pop
    for o in h:
        val = o.value
        if isinstance(val, KV):
            k = val.key
            if o.is_invoke:
                pending[o.process] = k
            else:
                pop(o.process, None)
            v = val.value
        elif not o.is_invoke and o.process in pending:
            k = pop(o.process)
            v = val
        else:
            continue
        lst = per_key.get(k)
        if lst is None:
            per_key[k] = lst = []
        lst.append(o.replace(value=v))
    return {k: History(ops, reindex=False) for k, ops in per_key.items()}


class IndependentChecker(Checker):
    """Applies `base` to each key's subhistory and merges validity
    (independent.clj:327-377).

    Fast path: if `base` is a Linearizable checker whose model packs to
    int32 form, every key is packed and decided in one batched device
    search sharded over the mesh; only keys the beam search could not
    settle fall back to the exact CPU search (still sound).  Any other
    checker runs per-key under bounded_pmap, like the reference.
    """

    def __init__(self, base: Checker, *, bound: Optional[int] = None,
                 streaming: bool = True):
        self.base = base
        self.bound = bound
        #: Consume online verdicts from a run's StreamingSession
        #: (jepsen_tpu/streaming/) when one is present in the test map.
        #: Off means every key settles post-hoc even on streamed runs.
        self.streaming = streaming

    def check(self, test: dict, history: History, opts: dict) -> dict:
        with telemetry.span("ingest.split"):
            subs = subhistories(history)
        keys = list(subs)
        if not keys:
            return {"valid": True, "results": {}, "key-count": 0}

        from ..ops import degrade

        results: dict[Any, dict]
        # The capture collects degradation-ladder steps taken by the
        # shared tiers (stream witness / batched BFS) that run on this
        # thread, outside any single key's Linearizable.check.
        with degrade.capture() as steps:
            if isinstance(self.base, Linearizable):
                results = self._check_linearizable(test, subs, opts)
            else:
                rs = bounded_pmap(
                    lambda k: check_safe(
                        self.base, test, subs[k], {**opts, "history_key": k}
                    ),
                    keys,
                    bound=self.bound,
                )
                results = dict(zip(keys, rs))

        valid = merge_valid(r.get("valid") for r in results.values())
        failures = [k for k, r in results.items() if r.get("valid") is False]
        self._write_key_artifacts(opts, subs, results)
        out = {
            "valid": valid,
            "key-count": len(keys),
            "failures": failures[:32],
            "failure-count": len(failures),
            "results": results,
        }
        if steps:
            out["degradations"] = steps
        return out

    #: Per-key artifact budget: failed keys always write; passing keys
    #: only up to this many (the reference writes every key's dir,
    #: independent.clj:355-364, but per-key workloads here can carry
    #: tens of thousands of keys).
    MAX_OK_KEY_DIRS = 256

    def _write_key_artifacts(self, opts: dict, subs: dict,
                             results: dict) -> None:
        """store/<test>/independent/<key>/{results.json,history.txt}
        per key, like the reference's per-key dirs.  Failures never
        raise: a side-output must not change the verdict."""
        import json
        import logging
        import os

        import hashlib

        from ..utils import sanitize_path_part

        directory = (opts or {}).get("dir")
        if not directory:
            return
        log = logging.getLogger(__name__)

        def jsonable_keys(x):
            # json.dump coerces dict VALUES via default=, never KEYS;
            # skipkeys would silently drop diagnostic entries.
            if isinstance(x, dict):
                return {
                    k if isinstance(k, str) else repr(k):
                        jsonable_keys(v)
                    for k, v in x.items()
                }
            if isinstance(x, (list, tuple)):
                return [jsonable_keys(v) for v in x]
            return x

        ok_written = 0
        used: set = set()
        for k, res in results.items():
            # Only fully-passing keys count against the budget:
            # False AND "unknown" verdicts are exactly the ones a
            # maintainer must inspect, so they always write.
            budgeted = res.get("valid") is True
            if budgeted and ok_written >= self.MAX_OK_KEY_DIRS:
                continue
            safe = sanitize_path_part(k)[:80]
            if safe in used:
                # Disambiguate truncation collisions with a stable
                # digest of the full key, keeping names bounded.
                digest = hashlib.sha1(
                    repr(k).encode()
                ).hexdigest()[:10]
                safe = f"{safe[:69]}-{digest}"
            used.add(safe)
            # Per-key isolation: one key's write failure (quota,
            # unserializable value, hostile op repr) must neither
            # skip later keys nor — via check_safe — replace the
            # computed verdict with "unknown".
            try:
                d = os.path.join(directory, "independent", safe)
                os.makedirs(d, exist_ok=True)
                with open(os.path.join(d, "results.json"), "w") as f:
                    json.dump(jsonable_keys(res), f, indent=2,
                              default=repr)
                with open(os.path.join(d, "history.txt"), "w",
                          errors="replace") as f:
                    for o in subs.get(k, ()):
                        f.write(str(o) + "\n")
                if budgeted:
                    ok_written += 1  # only successful writes consume budget
            except Exception as e:  # noqa: BLE001 — side output only
                log.warning(
                    "could not write artifacts for key %r: %r", k, e
                )

    # -- batched device path ------------------------------------------------

    def _check_linearizable(
        self, test: dict, subs: dict[Any, History], opts: dict
    ) -> dict[Any, dict]:
        lin = self.base
        model = lin.model or test.get("model")
        keys = list(subs)
        try:
            pm = model.packed()
        except (NotImplementedError, AttributeError):
            pm = None
        if pm is None or lin.algorithm in ("wgl", "linear", "cpu",
                                           "event", "settle"):
            rs = bounded_pmap(
                lambda k: check_safe(
                    lin, test, subs[k], {**opts, "history_key": k}
                ),
                keys,
                bound=self.bound,
            )
            return dict(zip(keys, rs))

        from ..history.packed import pack_history

        all_packs = {}
        unpackable = []
        with telemetry.span("ingest.pack", keys=len(keys)):
            for k in keys:
                try:
                    p = pack_history(subs[k], pm.encode)
                except ValueError:
                    # e.g. an indeterminate dequeue: no packed form for
                    # this key — the single-key checker falls back to
                    # the host-model search itself.
                    unpackable.append(k)
                    continue
                if pm.validate_packed is not None and \
                        pm.validate_packed(p) is not None:
                    unpackable.append(k)
                    continue
                all_packs[k] = p

        # The plan executor (jepsen_tpu/plan/) runs the ladder —
        # online consume, long-key split, stream witness, screens,
        # batched BFS, exact settle — as a pass DAG, with cost-model
        # knobs and (opt-in) persistent memoization.
        from ..plan.compiler import run_cohort

        return run_cohort(
            self, test, subs, [k for k in keys if k in all_packs],
            unpackable, all_packs, model, pm, lin, opts,
        )


def independent_checker(base: Checker, **kw: Any) -> IndependentChecker:
    return IndependentChecker(base, **kw)
