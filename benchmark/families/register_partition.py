"""The cas-register family under a partition nemesis: seeded histories
whose `:info` ops come from network partitions, not from a fixed rate.

A configuration of this family gives the cluster and the nemesis:
`threads` worker threads, thread t bound to node t mod `nodes`;
`phase_ops` invocations a phase, healthy and partitioned phases in
turn, healthy first, as `jepsen.nemesis/partition-random-halves` cuts
the cluster on a fixed interval; `minority_nodes` nodes cut off in each
partitioned phase, a fresh random set each time.  In a partitioned
phase a thread bound to a cut-off node times out: its writes and cas
complete `:info`, its reads `:fail`.  A thread whose op completes
`:info` takes the process `process + threads`, as Jepsen's worker does
after a crash, so a history holds thousands of process ids.

Everything else is the register family's (`register.py`): the op mix,
the values, the rule that an `:info` op takes effect at its completion
with probability 1/2 or never, the recorded linearization, the system
under test, the reference and the control.
"""

from __future__ import annotations

import random

from benchmark.families.register import (  # noqa: F401 — the harness
    # reads the system, reference, control and program input from here.
    Control,
    Entry,
    KeyHistory,
    System,
    engines,
    n_invocations,
    reference_verdicts,
    to_program,
)


def cut_off(config: dict, rng: random.Random, n_phases: int) -> list:
    """Each phase's set of cut-off nodes: empty in a healthy phase (the
    even ones), `minority_nodes` of the `nodes` drawn afresh in a
    partitioned one."""
    nodes, k = config["nodes"], config["minority_nodes"]
    return [frozenset(rng.sample(range(nodes), k)) if i % 2 else frozenset()
            for i in range(n_phases)]


def key_history(rng: random.Random, config: dict, cuts: list,
                bad: bool) -> KeyHistory:
    """One key under the nemesis, recording the linearization.  A
    thread's fate is set when it invokes, by the phase of that
    invocation: cut off, its write or cas completes `:info` and its
    read `:fail`; otherwise its write or cas completes `:info` with
    `healthy_info_rate`.  Each op applies at its completion; an `:info`
    op there with probability 1/2, or never."""
    n_ops, threads = config["ops_per_key"], config["threads"]
    nodes, n_values = config["nodes"], config["values"]
    phase_ops, healthy = config["phase_ops"], config["healthy_info_rate"]
    proc = list(range(threads))        # thread -> its current process
    value = None
    events: list = []
    witness: list = []
    pending: dict = {}   # thread -> (f, payload, outcome, invoke index)
    started = 0

    def complete(t: int) -> None:
        nonlocal value
        f, payload, outcome, at = pending.pop(t)
        p = proc[t]
        if outcome == "fail":
            events.append(("fail", f, payload, p))
            return
        if outcome == "info":
            if f == "write" and rng.random() < 0.5:
                value = payload
                witness.append(at)
            elif f == "cas" and rng.random() < 0.5 and value == payload[0]:
                value = payload[1]
                witness.append(at)
            events.append(("info", f, payload, p))
            proc[t] = p + threads
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
        t = rng.randrange(threads)
        if t in pending:
            complete(t)
        elif started < n_ops:
            f = rng.choice(("read", "write", "cas"))
            if f == "read":
                payload = None
            elif f == "write":
                payload = rng.randrange(n_values)
            else:
                payload = (rng.randrange(n_values), rng.randrange(n_values))
            if t % nodes in cuts[started // phase_ops]:
                outcome = "fail" if f == "read" else "info"
            elif f != "read" and healthy and rng.random() < healthy:
                outcome = "info"
            else:
                outcome = "ok"
            pending[t] = (f, payload, outcome, len(events))
            events.append(("invoke", f, payload, proc[t]))
            started += 1
    if bad:
        # A read of a value no op writes, after everything else: no
        # linearization exists.
        events.append(("invoke", "read", None, proc[0]))
        events.append(("ok", "read", n_values + 94, proc[0]))
    return KeyHistory(events, witness, bad)


def generate(config: dict, traffic: dict, seed: int) -> list:
    """The pool for one run: `traffic["pool"]` histories, each of
    `config["keys"]` keys, each key under its own nemesis schedule.
    `bad_key_share` of the keys end with a read of a value nothing
    writes.  Every seed gives the same sizes and the same number of
    planted keys; which keys, which nodes are cut off and the op order
    differ."""
    n_keys = config["keys"]
    n_bad = round(n_keys * traffic["bad_key_share"])
    n_phases = -(-config["ops_per_key"] // config["phase_ops"])
    pool = []
    for h in range(traffic["pool"]):
        bad = set(random.Random(f"{seed}/{h}/bad").sample(range(n_keys),
                                                          n_bad))
        keys = []
        for k in range(n_keys):
            cuts = cut_off(config, random.Random(f"{seed}/{h}/{k}/nemesis"),
                           n_phases)
            keys.append(key_history(random.Random(f"{seed}/{h}/{k}"),
                                    config, cuts, k in bad))
        pool.append(Entry(keys, sum(n_invocations(kh) for kh in keys)))
    return pool
