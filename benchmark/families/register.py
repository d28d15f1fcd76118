"""The cas-register family: seeded histories, the system under test, and
its plain reference.

A configuration of this family (`benchmark/configs/*.json`) gives the
shape: keys, ops per key, processes, the `:info` rate, the value range,
and the checker (`linearizable` for one object, `independent` for
`jepsen.independent` keys).  A traffic mix (`benchmark/traffic/*.json`)
gives the pool (how many distinct histories a run cycles through) and
the share of keys that carry a planted unsupported read.

Two generators, each recording the linearization it builds: every op
takes effect at one instant between its invocation and its completion,
so the order of those instants is a certificate that the reference
checks.  `key_history` is a copy of `jepsen_tpu/utils/histgen.py`'s
`random_register_history` (the program's may change under later PRs),
for a configuration without `"mix"`.  `clj_key_history` generates a key
as Jepsen's `tests/linearizable_register.clj` does, for `"mix": "clj"`.
Events are plain tuples `(type, f, value, process)`; only `to_program`
turns them into the program's `Op`s.
"""

from __future__ import annotations

import math
import random
from dataclasses import dataclass

from benchmark.reference import cas_register as ref


@dataclass
class KeyHistory:
    events: list        # [(type, f, value, process)], invocation order
    witness: list       # invoke-event indices, in linearization order
    bad: bool           # carries a planted violation


@dataclass
class Entry:
    """One history of the pool: what the program is given, and what
    the reference reads."""
    keys: list          # [KeyHistory], one per key
    n_ops: int          # invocations over all keys
    program_input: object = None


def key_history(rng: random.Random, n_ops: int, procs: int,
                info_rate: float, n_values: int, bad: bool) -> KeyHistory:
    """`random_register_history`'s state machine, recording the
    linearization.  Each op applies at its completion; an `:info` op
    applies there with probability 1/2, or never."""
    value = None
    events: list = []
    witness: list = []
    pending: dict = {}   # process -> (f, payload, as_info, invoke index)
    started = 0

    def complete(p: int) -> None:
        nonlocal value
        f, payload, as_info, at = pending.pop(p)
        if as_info:
            if f == "write" and rng.random() < 0.5:
                value = payload
                witness.append(at)
            elif f == "cas" and rng.random() < 0.5 and value == payload[0]:
                value = payload[1]
                witness.append(at)
            events.append(("info", f, payload, p))
            return
        if f == "read":
            events.append(("ok", "read", value, p))
            witness.append(at)
        elif f == "write":
            value = payload
            events.append(("ok", "write", payload, p))
            witness.append(at)
        elif value == payload[0]:
            value = payload[1]
            events.append(("ok", "cas", payload, p))
            witness.append(at)
        else:
            events.append(("fail", "cas", payload, p))

    while started < n_ops or pending:
        p = rng.randrange(procs)
        if p in pending:
            complete(p)
        elif started < n_ops:
            f = rng.choice(("read", "write", "cas"))
            if f == "read":
                payload = None
            elif f == "write":
                payload = rng.randrange(n_values)
            else:
                payload = (rng.randrange(n_values), rng.randrange(n_values))
            as_info = f != "read" and rng.random() < info_rate
            pending[p] = (f, payload, as_info, len(events))
            events.append(("invoke", f, payload, p))
            started += 1
    if bad:
        # A read of a value no op writes, after everything else: no
        # linearization exists (histgen's `bad=True`).
        events.append(("invoke", "read", None, 0))
        events.append(("ok", "read", n_values + 94, 0))
    return KeyHistory(events, witness, bad)


def clj_key_history(rng: random.Random, limit: int, config: dict,
                    bad: bool) -> KeyHistory:
    """One key of `linearizable_register.clj`'s generator:
    `(gen/reserve n r (gen/mix [w cas cas]))` over `threads_per_key`
    threads, so the first `readers_per_key` threads only read and the
    rest write or cas at 1:2, values `(rand-int 5)`; `(gen/limit
    limit)`; `(gen/process-limit 20)`.  A thread whose op completes
    `:info` takes the process `process + threads_per_key`, as Jepsen's
    worker does after a crash; a thread whose next process would pass
    the process limit stops.  Each op applies at its completion; an
    `:info` op there with probability 1/2, or never."""
    threads = config["threads_per_key"]
    readers = config["readers_per_key"]
    n_values = config["values"]
    info_rate = config["info_rate"]
    mix = config["writer_mix"]
    proc = list(range(threads))        # thread -> its current process
    used = set(proc)
    live = list(range(threads))        # threads that may still run
    value = None
    events: list = []
    witness: list = []
    pending: dict = {}   # thread -> (f, payload, as_info, invoke index)
    started = 0

    def complete(t: int) -> None:
        nonlocal value
        f, payload, as_info, at = pending.pop(t)
        p = proc[t]
        if as_info:
            if f == "write" and rng.random() < 0.5:
                value = payload
                witness.append(at)
            elif f == "cas" and rng.random() < 0.5 and value == payload[0]:
                value = payload[1]
                witness.append(at)
            events.append(("info", f, payload, p))
            proc[t] = p + threads
            if proc[t] not in used and len(used) >= config["process_limit"]:
                live.remove(t)
            used.add(proc[t])
            return
        if f == "read":
            events.append(("ok", "read", value, p))
            witness.append(at)
        elif f == "write":
            value = payload
            events.append(("ok", "write", payload, p))
            witness.append(at)
        elif value == payload[0]:
            value = payload[1]
            events.append(("ok", "cas", payload, p))
            witness.append(at)
        else:
            events.append(("fail", "cas", payload, p))

    while live and (started < limit or pending):
        t = rng.choice(live)
        if t in pending:
            complete(t)
        elif started < limit:
            f = "read" if t < readers else rng.choice(mix)
            if f == "read":
                payload = None
            elif f == "write":
                payload = rng.randrange(n_values)
            else:
                payload = (rng.randrange(n_values), rng.randrange(n_values))
            as_info = f != "read" and rng.random() < info_rate
            pending[t] = (f, payload, as_info, len(events))
            events.append(("invoke", f, payload, proc[t]))
            started += 1
    if bad:
        events.append(("invoke", "read", None, 0))
        events.append(("ok", "read", n_values + 94, 0))
    return KeyHistory(events, witness, bad)


def hidden_violation(kh: KeyHistory, n_values: int, readers: int,
                     writers: tuple) -> KeyHistory:
    """`kh` behind a prefix that no linearization admits, and that no
    screen of single reads can refute: two concurrent writes of values
    nothing else writes, `a` and `b`, and three reads inside them, one
    after another, of b, a, b.  Each read has a producer that is never
    overwritten before it; only a search sees that b cannot come back.
    The device search has to refute the key."""
    a, b = n_values, n_values + 1
    w0, w1 = writers
    pre = [("invoke", "write", a, w0), ("invoke", "write", b, w1)]
    for i, v in enumerate((b, a, b)):
        pre += [("invoke", "read", None, i % readers), ("ok", "read", v,
                                                        i % readers)]
    pre += [("ok", "write", a, w0), ("ok", "write", b, w1)]
    return KeyHistory(pre + kh.events, [], True)


def key_limits(config: dict, rng: random.Random) -> list:
    """Each key's op limit, `(* (+ (rand 0.1) 0.9) per-key-limit)` as
    `gen/limit` counts it (ceiling), drawn as one fixed set of quantiles
    in an order from `rng`: every seed gives the same sizes."""
    lo, hi = config["limit_scale"]
    n = config["keys"]
    lim = config["per_key_limit"]
    out = [math.ceil(lim * (lo + (hi - lo) * (i + 0.5) / n))
           for i in range(n)]
    rng.shuffle(out)
    return out


def generate(config: dict, traffic: dict, seed: int) -> list:
    """The pool for one run: `traffic["pool"]` histories, each of
    `config["keys"]` keys.  `bad_key_share` of the keys end with a read
    of a value nothing writes; `hidden_bad_key_share` of the others
    carry `hidden_violation`.  Every seed gives the same sizes and the
    same number of planted keys; only which keys and the op order
    differ."""
    n_keys = config["keys"]
    n_bad = round(n_keys * traffic["bad_key_share"])
    n_hidden = round(n_keys * traffic.get("hidden_bad_key_share", 0.0))
    clj = config.get("mix") == "clj"
    pool = []
    for h in range(traffic["pool"]):
        pick = random.Random(f"{seed}/{h}/bad").sample(range(n_keys),
                                                       n_bad + n_hidden)
        bad, hidden = set(pick[:n_bad]), set(pick[n_bad:])
        if clj:
            limits = key_limits(config, random.Random(f"{seed}/{h}/limits"))
        keys = []
        for k in range(n_keys):
            rng = random.Random(f"{seed}/{h}/{k}")
            if clj:
                kh = clj_key_history(rng, limits[k], config, k in bad)
            else:
                kh = key_history(rng, config["ops_per_key"],
                                 config["processes"], config["info_rate"],
                                 config["values"], k in bad)
            if k in hidden:
                kh = hidden_violation(kh, config["values"],
                                      config["readers_per_key"],
                                      (config["readers_per_key"],
                                       config["readers_per_key"] + 1))
            keys.append(kh)
        pool.append(Entry(keys, sum(n_invocations(kh) for kh in keys)))
    return pool


def n_invocations(kh: KeyHistory) -> int:
    return sum(e[0] == "invoke" for e in kh.events)


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------


def to_program(config: dict, entry: Entry):
    """The entry as the program's History.  An `independent` config
    runs its keys one after another, as `jepsen.independent` does with
    one key group of `processes` threads."""
    from jepsen_tpu.history.core import Op, history
    from jepsen_tpu.parallel.independent import kv

    ops = []
    keyed = config["checker"] == "independent"
    for k, kh in enumerate(entry.keys):
        for typ, f, value, p in kh.events:
            ops.append(Op(type=typ, f=f, process=p,
                          value=kv(k, value) if keyed else value))
    return history(ops)


def make_test(config: dict) -> dict:
    from jepsen_tpu.checker.linearizable import linearizable
    from jepsen_tpu.models import cas_register
    from jepsen_tpu.parallel.independent import independent_checker

    checker = linearizable(cas_register())
    if config["checker"] == "independent":
        checker = independent_checker(checker)
    return {"name": "benchmark", "model": cas_register(),
            "checker": checker}


class System:
    """`core.analyze` as a user calls it, one history per call.  The
    test names no store directory, so the call returns at the verdict
    and writes no dossier (PERF.md says why).  The settle memo is
    cleared before each call: no verdict is replayed."""

    def __init__(self, config: dict):
        from jepsen_tpu import core
        from jepsen_tpu.parallel.independent import clear_settle_memo

        self._analyze = core.analyze
        self._clear = clear_settle_memo
        self.test = make_test(config)
        self.config = config

    def check(self, entry: Entry):
        self._clear()
        return self._analyze(self.test, entry.program_input)

    def verdicts(self, result) -> list:
        """Per-key verdicts, in key order; a key the result leaves out
        reads None."""
        if self.config["checker"] != "independent":
            return [result.get("valid")]
        per_key = result.get("results") or {}
        return [(per_key.get(k) or {}).get("valid")
                for k in range(self.config["keys"])]


def engines(result) -> list:
    """Every "algorithm" named anywhere in a checker result tree."""
    out = []
    if isinstance(result, dict):
        if isinstance(result.get("algorithm"), str):
            out.append(result["algorithm"])
        for v in result.values():
            out += engines(v)
    elif isinstance(result, list):
        for v in result:
            out += engines(v)
    return out


# ---------------------------------------------------------------------------
# The reference, and the control that breaks the :info guarantee
# ---------------------------------------------------------------------------


def reference_verdicts(entry: Entry, info_as_fail: bool = False) -> list:
    return [ref.decide(kh.events, kh.witness, info_as_fail=info_as_fail)
            for kh in entry.keys]


class Control:
    """The reference in the program's place, with one guarantee broken:
    an `:info` op is taken never to have happened, as if it had
    failed.  Shaped like `System` so the harness drives it the same
    way."""

    def __init__(self, config: dict):
        self.config = config

    def check(self, entry: Entry):
        got = reference_verdicts(entry, info_as_fail=True)
        if self.config["checker"] != "independent":
            return {"valid": got[0]}
        return {"valid": all(v is True for v in got),
                "results": {k: {"valid": v} for k, v in enumerate(got)}}

    verdicts = System.verdicts
