"""Pallas witness-sweep parity (ops/wgl_witness.py `pallas` modes).

On the CPU test mesh the kernel runs in interpreter mode — same
program, emulated — and must agree exactly with the XLA-scan sweep.
The real Mosaic compile is checked for a described v5e in
tests/test_tpu_compile.py, and run on the chip by chip_smoke.py.
"""

import pytest

from jepsen_tpu.history.packed import pack_history
from jepsen_tpu.models import cas_register, multi_register, register
from jepsen_tpu.ops.wgl_witness import check_wgl_witness
from jepsen_tpu.utils.histgen import random_register_history


def _verdict(r):
    return None if r is None else r.valid


@pytest.mark.parametrize(
    "n,info,procs,seed",
    [
        (256, 0.0, 4, 1),
        (1024, 0.1, 8, 2),
        (2048, 0.3, 16, 3),   # heavy chain rounds interleave the sweep
        (4096, 0.05, 8, 4),
    ],
)
def test_interpret_parity_cas(n, info, procs, seed):
    pm = cas_register().packed()
    h = random_register_history(n, procs=procs, info_rate=info, seed=seed)
    p = pack_history(h, pm.encode)
    a = check_wgl_witness(p, pm, pallas="off")
    b = check_wgl_witness(p, pm, pallas="interpret")
    assert _verdict(a) == _verdict(b)
    assert _verdict(a) in (True, None)


def test_interpret_parity_invalid_dies_both_ways():
    pm = cas_register().packed()
    h = random_register_history(
        256, procs=4, info_rate=0.0, seed=13, bad=True
    )
    p = pack_history(h, pm.encode)
    # Witness tier can only say True or None; invalid histories die.
    assert check_wgl_witness(p, pm, pallas="off") is None
    assert check_wgl_witness(p, pm, pallas="interpret") is None


def test_interpret_parity_plain_register():
    rm = register().packed()
    h = random_register_history(
        1024, procs=8, info_rate=0.1, seed=21, cas=False
    )
    p = pack_history(h, rm.encode)
    a = check_wgl_witness(p, rm, pallas="off")
    b = check_wgl_witness(p, rm, pallas="interpret")
    assert _verdict(a) == _verdict(b) is True


def test_multi_register_rows_step_parity():
    """jax_step_rows (lane-major, scatter-free) must agree with
    vmap(jax_step) for the multi-register model."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    pm = multi_register({"x": 0, "y": 1, "z": 2}).packed()
    rng = np.random.default_rng(7)
    B = 8
    states = jnp.asarray(
        rng.integers(0, 5, size=(B, pm.state_width)), jnp.int32
    )
    for f, a0, a1 in ((0, 1, 3), (1, 2, 4), (0, 0, 0)):
        ns_v, legal_v = jax.vmap(
            lambda s: pm.jax_step(s, f, a0, a1)
        )(states)
        ns_r, legal_r = pm.jax_step_rows(states.T, f, a0, a1)
        assert (np.asarray(ns_r.T) == np.asarray(ns_v)).all()
        assert (np.asarray(legal_r) == np.asarray(legal_v)).all()


def test_mutex_rows_step_parity_and_witness():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from jepsen_tpu.models import mutex

    pm = mutex().packed()
    states = jnp.asarray([[0], [1], [0], [1]], jnp.int32)
    for f in (0, 1):
        ns_v, legal_v = jax.vmap(lambda s: pm.jax_step(s, f, 0, 0))(states)
        ns_r, legal_r = pm.jax_step_rows(states.T, f, 0, 0)
        assert (np.asarray(ns_r.T) == np.asarray(ns_v)).all()
        assert (np.asarray(legal_r) == np.asarray(legal_v)).all()

    # Sequential acquire/release across processes: linearizable; the
    # interpret-mode kernel must agree with the scan sweep.
    from jepsen_tpu.history import History, Op, INVOKE, OK

    rows = []
    for i in range(64):
        p = i % 4
        rows += [
            Op(type=INVOKE, f="acquire", process=p),
            Op(type=OK, f="acquire", process=p),
            Op(type=INVOKE, f="release", process=p),
            Op(type=OK, f="release", process=p),
        ]
    p = pack_history(History(rows), pm.encode)
    a = check_wgl_witness(p, pm, pallas="off")
    b = check_wgl_witness(p, pm, pallas="interpret")
    assert _verdict(a) == _verdict(b) is True


def test_pallas_runtime_failure_raises(monkeypatch):
    """A Mosaic failure mid-search in 'on' mode surfaces: nothing reruns
    the search on the XLA-scan sweep."""
    import jepsen_tpu.ops.wgl_witness as w

    pm = cas_register().packed()
    h = random_register_history(512, procs=4, info_rate=0.1, seed=9)
    p = pack_history(h, pm.encode)

    real_make = w._make_chunk_fn
    calls = []

    def fake_make(B, W, SW, K, D, NB, jax_step, pallas_mode="off",
                  jax_step_rows=None, packed=False):
        calls.append(pallas_mode)
        if pallas_mode == "on":
            def boom(*a, **k):
                raise RuntimeError("Mosaic failed to compile TPU kernel")
            # Real contract: (fn, fn_idx, make_dev) — all must blow up
            # at CALL time (the jitted dispatch path), not build time.
            return boom, boom, boom
        return real_make(B, W, SW, K, D, NB, jax_step,
                         pallas_mode=pallas_mode,
                         jax_step_rows=jax_step_rows, packed=packed)

    monkeypatch.setattr(w, "_make_chunk_fn", fake_make)
    w._chunk_fn_cache.clear()
    try:
        with pytest.raises(RuntimeError, match="Mosaic failed"):
            w.check_wgl_witness(p, pm, pallas="on")
    finally:
        w._chunk_fn_cache.clear()
    assert calls == ["on"]


def test_pallas_build_failure_raises(monkeypatch):
    """A failure while BUILDING the Pallas kernel (pallas_call
    construction / Mosaic lowering, before any chunk executes) raises
    too, and is not remembered: the next check builds again."""
    import jepsen_tpu.ops.wgl_witness as w

    pm = cas_register().packed()
    h = random_register_history(512, procs=4, info_rate=0.1, seed=9)
    p = pack_history(h, pm.encode)

    real_make = w._make_chunk_fn
    calls = []

    def fake_make(B, W, SW, K, D, NB, jax_step, pallas_mode="off",
                  jax_step_rows=None, packed=False):
        calls.append(pallas_mode)
        if pallas_mode == "on":
            raise RuntimeError("Mosaic lowering rejected kernel")
        return real_make(B, W, SW, K, D, NB, jax_step,
                         pallas_mode=pallas_mode,
                         jax_step_rows=jax_step_rows, packed=packed)

    monkeypatch.setattr(w, "_make_chunk_fn", fake_make)
    w._chunk_fn_cache.clear()
    try:
        for _ in range(2):
            with pytest.raises(RuntimeError, match="Mosaic lowering"):
                w.check_wgl_witness(p, pm, pallas="on")
        assert calls == ["on", "on"]
    finally:
        w._chunk_fn_cache.clear()


def test_pallas_build_failure_off_mode_raises(monkeypatch):
    """Build failures under pallas='off' are programming errors and
    must surface, not silently recurse."""
    import jepsen_tpu.ops.wgl_witness as w

    pm = cas_register().packed()
    h = random_register_history(128, procs=4, info_rate=0.0, seed=3)
    p = pack_history(h, pm.encode)

    def fake_make(*a, **k):
        raise RuntimeError("synthetic build failure")

    monkeypatch.setattr(w, "_make_chunk_fn", fake_make)
    w._chunk_fn_cache.clear()
    try:
        with pytest.raises(RuntimeError, match="synthetic build"):
            w.check_wgl_witness(p, pm, pallas="off")
    finally:
        w._chunk_fn_cache.clear()


def test_models_without_rows_step_fall_back():
    """A model with no Mosaic-safe batched step (round-4: every
    shipped model now has one, so strip it artificially) must degrade
    to the scan sweep under pallas='interpret' instead of erroring."""
    import dataclasses

    from jepsen_tpu.models import unordered_queue

    pm = unordered_queue().packed()
    pm = dataclasses.replace(pm, jax_step_rows=None)
    from jepsen_tpu.history import parse_literal, INVOKE, OK

    h = parse_literal([
        (0, INVOKE, "enqueue", 1), (0, OK, "enqueue", 1),
        (1, INVOKE, "dequeue", None), (1, OK, "dequeue", 1),
    ])
    p = pack_history(h, pm.encode)
    r = check_wgl_witness(p, pm, pallas="interpret")
    assert _verdict(r) is True


def test_unordered_queue_rows_step_parity_and_witness():
    """The round-4 sort-free unordered rows step: per-(state, op)
    parity with jax_step up to multiset equality (the rows step does
    not re-sort — by design, see collections.py), and a witness run
    through the interpret-mode Pallas kernel."""
    import itertools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from jepsen_tpu.models import unordered_queue

    pm = unordered_queue().packed()
    C = pm.state_width
    lanes = []
    for fill in range(3):
        for vals in itertools.product((2, 3), repeat=fill):
            lanes.append([0] * (C - fill) + sorted(vals))
    F_ENQ, F_DEQ = 0, 1
    cases = [(F_ENQ, 2), (F_ENQ, 4), (F_DEQ, 2), (F_DEQ, 3),
             (F_DEQ, 9)]
    for f, a0 in cases:
        states = jnp.asarray(np.array(lanes, dtype=np.int32)).T
        rows_new, rows_legal = pm.jax_step_rows(
            states, jnp.int32(f), jnp.int32(a0), jnp.int32(0)
        )
        for i, lane in enumerate(lanes):
            ref_new, ref_legal = jax.jit(pm.jax_step)(
                jnp.asarray(lane, jnp.int32), jnp.int32(f),
                jnp.int32(a0), jnp.int32(0),
            )
            assert bool(ref_legal) == bool(rows_legal[i] != 0), (
                f, a0, lane
            )
            if bool(ref_legal):
                # Multiset equality: the rows step is sort-free.
                assert sorted(np.asarray(rows_new[:, i]).tolist()) \
                    == sorted(np.asarray(ref_new).tolist()), (
                        f, a0, lane,
                    )

    # End-to-end witness through the interpret-mode kernel.
    from jepsen_tpu.history import parse_literal, INVOKE, OK

    h = parse_literal([
        (0, INVOKE, "enqueue", 1), (0, OK, "enqueue", 1),
        (2, INVOKE, "enqueue", 5), (2, OK, "enqueue", 5),
        (1, INVOKE, "dequeue", None), (1, OK, "dequeue", 5),
        (3, INVOKE, "dequeue", None), (3, OK, "dequeue", 1),
    ])
    p = pack_history(h, pm.encode)
    r = check_wgl_witness(p, pm, pallas="interpret")
    assert _verdict(r) is True


def test_fifo_queue_rows_step_parity_and_witness():
    import itertools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from jepsen_tpu.models import fifo_queue

    pm = fifo_queue().packed()
    C = pm.state_width
    # Exhaustive-ish states: left-aligned queues of codes 0..3.
    lanes = []
    for fill in range(min(C, 3) + 1):
        for vals in itertools.product((2, 3, 4), repeat=fill):
            lanes.append(list(vals) + [0] * (C - fill))
    states = jnp.asarray(lanes, jnp.int32)
    for f, a0 in ((0, 2), (0, 5), (1, 2), (1, 3)):
        ns_v, legal_v = jax.vmap(
            lambda s: pm.jax_step(s, f, a0, 0)
        )(states)
        ns_r, legal_r = pm.jax_step_rows(states.T, f, a0, 0)
        assert (np.asarray(ns_r.T) == np.asarray(ns_v)).all(), (f, a0)
        assert (
            np.asarray(legal_r).astype(bool)
            == np.asarray(legal_v).astype(bool)
        ).all(), (f, a0)

    # Witness interpret parity on a concurrent producer/consumer run.
    from jepsen_tpu.history import History, Op, INVOKE, OK

    rows = []
    for i in range(128):
        rows += [
            Op(type=INVOKE, f="enqueue", value=i, process=0),
            Op(type=OK, f="enqueue", value=i, process=0),
            Op(type=INVOKE, f="dequeue", process=1),
            Op(type=OK, f="dequeue", value=i, process=1),
        ]
    p = pack_history(History(rows), pm.encode)
    a = check_wgl_witness(p, pm, pallas="off")
    b = check_wgl_witness(p, pm, pallas="interpret")
    assert _verdict(a) == _verdict(b) is True


def test_bars_per_block_beyond_smem_raises_before_build(monkeypatch):
    """An explicit bars_per_block whose barrier table cannot fit the
    Pallas sweep's SMEM is refused before any kernel is built."""
    import jepsen_tpu.ops.wgl_witness as w

    pm = cas_register().packed()
    p = pack_history(random_register_history(256, procs=4, seed=5),
                     pm.encode)

    def no_build(*a, **k):
        raise AssertionError("kernel built")

    monkeypatch.setattr(w, "_make_chunk_fn", no_build)
    with pytest.raises(ValueError, match="SMEM"):
        w.check_wgl_witness(p, pm, pallas="interpret",
                            bars_per_block=32768, blocks_per_call=4)
