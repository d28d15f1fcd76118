"""Bit-packed WGL kernels: packing primitives, engine parity, the
packed -> wide degradation rung, and the columnar ingest fast path.

The packed engines carry member/child bitsets as uint32 lane words
(ops/packing.py) instead of bool vectors.  The contract is byte-level
behavioural parity: for any history, the packed and wide variants of
every engine must produce the SAME verdicts AND the same exploration
counts (dedup is exact in both, so frontier sets are identical).  The
tests here run randomized differential trials across all four engines
(BFS, batched, witness, stream) against the exact CPU oracle, plus the
shape edges packing is most likely to get wrong: windows whose width is
not a multiple of 32, single-op and empty histories.
"""

from __future__ import annotations

import random
import zlib

import numpy as np
import pytest

from jepsen_tpu.checker.wgl_cpu import check_wgl_cpu
from jepsen_tpu.history import pack_history
from jepsen_tpu.history.core import Op, history
from jepsen_tpu.history.packed import (
    PackedBuilder,
    packed_to_bytes,
)
from jepsen_tpu.models import cas_register, mutex
from jepsen_tpu.ops import degrade, packing
from jepsen_tpu.ops.wgl import PACKED_ENV, check_wgl_device, packed_enabled
from jepsen_tpu.ops.wgl_batched import check_wgl_batched
from jepsen_tpu.ops.wgl_stream import check_wgl_witness_stream
from jepsen_tpu.ops.wgl_witness import check_wgl_witness
from jepsen_tpu.utils.histgen import random_register_history


# -- packing primitives ----------------------------------------------------


@pytest.mark.parametrize("W", [1, 2, 31, 32, 33, 63, 64, 65, 100, 256])
def test_pack_unpack_roundtrip(W):
    rng = np.random.default_rng(W)
    x = rng.random((5, W)) < 0.5
    words_np = packing.np_pack_bits(x)
    assert words_np.dtype == np.uint32
    assert words_np.shape == (5, packing.n_words(W))
    back = packing.np_unpack_bits(words_np, W)
    np.testing.assert_array_equal(back, x)
    # Device path agrees with the host mirror bit-for-bit.
    words_j = np.asarray(packing.pack_bits(x))
    np.testing.assert_array_equal(words_j, words_np)
    np.testing.assert_array_equal(
        np.asarray(packing.unpack_bits(words_j, W)), x
    )
    # Padding lanes beyond W are zero.
    padded = packing.np_unpack_bits(words_np, words_np.shape[-1] * 32)
    assert not padded[:, W:].any()


@pytest.mark.parametrize("W", [1, 31, 32, 33, 100])
def test_covers_popcount_set_bit_match_bool_semantics(W):
    rng = np.random.default_rng(1000 + W)
    child = rng.random((8, W)) < 0.6
    ok = rng.random((8, W)) < 0.4
    child_w = packing.pack_bits(child)
    ok_w = packing.pack_bits(ok)
    want_cover = (child | ~ok).all(axis=-1)
    np.testing.assert_array_equal(
        np.asarray(packing.covers(child_w, ok_w)), want_cover
    )
    np.testing.assert_array_equal(
        np.asarray(packing.popcount(child_w)), child.sum(axis=-1)
    )
    slots = rng.integers(0, W, size=8).astype(np.int32)
    got = packing.np_unpack_bits(
        np.asarray(packing.set_bit(child_w, slots)), W
    )
    want = child.copy()
    want[np.arange(8), slots] = True
    np.testing.assert_array_equal(got, want)


def test_hash_words_deterministic_and_stream_independent():
    consts0 = packing.hash_consts(4, 0)
    consts1 = packing.hash_consts(4, 1)
    assert consts0.dtype == np.uint32
    assert (consts0 % 2 == 1).all(), "multipliers must be odd"
    assert not np.array_equal(consts0, consts1)
    rng = np.random.default_rng(3)
    words = rng.integers(0, 1 << 32, size=(6, 4), dtype=np.uint32)
    h = np.asarray(packing.hash_words(words, consts0))
    assert h.dtype == np.uint32
    np.testing.assert_array_equal(
        h, np.asarray(packing.hash_words(words, consts0))
    )


# -- env gate --------------------------------------------------------------


def test_packed_enabled_gate(monkeypatch):
    monkeypatch.delenv(PACKED_ENV, raising=False)
    assert packed_enabled(None) is True  # default on
    monkeypatch.setenv(PACKED_ENV, "0")
    assert packed_enabled(None) is False
    # Explicit kwarg always wins over the env.
    assert packed_enabled(True) is True
    monkeypatch.setenv(PACKED_ENV, "1")
    assert packed_enabled(False) is False


# -- engine parity: packed vs wide vs exact CPU ----------------------------


def _register_trials(n_trials=8, procs=8):
    """Seeded register histories, half with an early injected
    violation (the verdict-mix floor needs settled Falses)."""
    rng = random.Random(zlib.crc32(b"wgl-packed") & 0xFFFF)
    out = []
    for rep in range(n_trials):
        h = random_register_history(
            140, procs=procs, info_rate=0.06,
            seed=rng.randrange(1 << 30),
            bad_at=rng.uniform(0.05, 0.3) if rep % 2 else None,
        )
        out.append(pack_history(h, cas_register().packed().encode))
    return out


def test_bfs_parity_packed_vs_wide_vs_cpu():
    pm = cas_register().packed()
    verdicts = {True: 0, False: 0}
    for packed in _register_trials():
        wide = check_wgl_device(
            packed, pm, witness=False, packed_lanes=False,
            time_limit_s=60.0,
        )
        lanes = check_wgl_device(
            packed, pm, witness=False, packed_lanes=True,
            time_limit_s=60.0,
        )
        assert lanes.valid == wide.valid
        # Dedup is exact in both variants, but the float-hash and the
        # uint32 wrap-hash collide differently, and collisions cost
        # beam slots — so under candidate-pool truncation the explored
        # counts may drift a little.  They must stay close.
        assert abs(lanes.configs_explored - wide.configs_explored) <= \
            max(64, wide.configs_explored // 10)
        cpu = check_wgl_cpu(packed, pm, time_limit_s=20.0)
        if "unknown" not in (cpu.valid, lanes.valid):
            assert lanes.valid is cpu.valid
            verdicts[cpu.valid] += 1
    assert verdicts[True] >= 2, verdicts
    assert verdicts[False] >= 2, verdicts


def test_bfs_parity_wide_window_not_multiple_of_32():
    # procs=40 drives window widths past 32 and (generically) off the
    # 32-lane boundary — the padding-lane edge of the packed cover.
    pm = cas_register().packed()
    rng = random.Random(0xBEEF)
    for rep in range(3):
        h = random_register_history(
            120, procs=40, info_rate=0.1, seed=rng.randrange(1 << 30),
            bad_at=0.2 if rep == 1 else None,
        )
        packed = pack_history(h, pm.encode)
        wide = check_wgl_device(
            packed, pm, witness=False, packed_lanes=False,
            time_limit_s=60.0,
        )
        lanes = check_wgl_device(
            packed, pm, witness=False, packed_lanes=True,
            time_limit_s=60.0,
        )
        assert lanes.valid == wide.valid
        # Wide windows truncate the candidate pool hard, so explored
        # counts legitimately diverge; cross-check the verdict against
        # the exact CPU oracle instead.
        cpu = check_wgl_cpu(packed, pm, time_limit_s=20.0)
        if "unknown" not in (cpu.valid, lanes.valid):
            assert lanes.valid is cpu.valid


def test_bfs_parity_single_op_and_empty():
    pm = cas_register().packed()
    empty = pack_history(history([]), pm.encode)
    single = pack_history(history([
        Op(type="invoke", f="write", value=7, process=0),
        Op(type="ok", f="write", value=7, process=0),
    ]), pm.encode)
    for packed in (empty, single):
        for lanes_on in (False, True):
            res = check_wgl_device(
                packed, pm, witness=False, packed_lanes=lanes_on,
            )
            assert res.valid is True


def test_batched_parity_packed_vs_wide():
    pm = cas_register().packed()
    packs = _register_trials(n_trials=10, procs=6)
    wide = check_wgl_batched(packs, pm, packed_lanes=False,
                             time_limit_s=120.0)
    lanes = check_wgl_batched(packs, pm, packed_lanes=True,
                              time_limit_s=120.0)
    assert lanes.valid == wide.valid
    assert lanes.explored.shape == wide.explored.shape
    # Same beam-truncation caveat as the BFS parity test above.
    drift = np.abs(lanes.explored.astype(np.int64)
                   - wide.explored.astype(np.int64))
    assert (drift <= np.maximum(64, wide.explored // 10)).all()
    for p, v in zip(packs, lanes.valid):
        if v == "unknown":
            continue
        cpu = check_wgl_cpu(p, pm, time_limit_s=20.0)
        if cpu.valid != "unknown":
            assert v is cpu.valid


def test_witness_parity_packed_vs_wide():
    pm = cas_register().packed()
    rng = random.Random(0xACE)
    decided = 0
    for _ in range(4):
        h = random_register_history(
            600, procs=8, info_rate=0.04, seed=rng.randrange(1 << 30),
        )
        packed = pack_history(h, pm.encode)
        info_w: dict = {}
        info_l: dict = {}
        wide = check_wgl_witness(packed, pm, packed_lanes=False,
                                 out_info=info_w, time_limit_s=60.0)
        lanes = check_wgl_witness(packed, pm, packed_lanes=True,
                                  out_info=info_l, time_limit_s=60.0)
        assert (wide is None) == (lanes is None)
        # The block semantics are bit-identical, so a died witness dies
        # at the same rank either way.
        assert info_w.get("died_at_rank") == info_l.get("died_at_rank")
        if wide is not None:
            assert wide.valid is lanes.valid is True
            decided += 1
    assert decided >= 1  # the soak must actually exercise survivors


def test_stream_parity_packed_vs_wide():
    pm = cas_register().packed()
    packs = _register_trials(n_trials=8, procs=6)
    wide = check_wgl_witness_stream(packs, pm, packed_lanes=False,
                                    time_limit_s=120.0)
    lanes = check_wgl_witness_stream(packs, pm, packed_lanes=True,
                                     time_limit_s=120.0)
    assert lanes == wide
    assert any(v is True for v in lanes)  # some keys must prove out


def test_mutex_parity_packed_vs_wide():
    # A second model family through the packed BFS: state transitions
    # differ (acquire/release legality), lane packing must not care.
    pm = mutex().packed()
    ops = []
    for round_ in range(30):
        p = round_ % 3
        ops.append(Op(type="invoke", f="acquire", value=None, process=p))
        ops.append(Op(type="ok", f="acquire", value=None, process=p))
        ops.append(Op(type="invoke", f="release", value=None, process=p))
        ops.append(Op(type="ok", f="release", value=None, process=p))
    packed = pack_history(history(ops), pm.encode)
    wide = check_wgl_device(packed, pm, witness=False,
                            packed_lanes=False)
    lanes = check_wgl_device(packed, pm, witness=False,
                             packed_lanes=True)
    assert lanes.valid is wide.valid is True
    assert lanes.configs_explored == wide.configs_explored


# -- degradation ladder: shed packing before beam --------------------------


def test_device_ladder_sheds_packing_first(monkeypatch):
    pm = cas_register().packed()
    h = random_register_history(120, procs=6, info_rate=0.05, seed=5)
    packed = pack_history(h, pm.encode)
    monkeypatch.setenv(degrade.FAULT_ENV, "device")
    with degrade.capture() as steps:
        res = check_wgl_device(
            packed, pm, witness=False, packed_lanes=True,
            time_limit_s=60.0,
        )
    actions = [(s["tier"], s["action"]) for s in steps]
    assert ("device", "packed-fallback") in actions
    # Packing is shed BEFORE any beam halving.
    first_fb = actions.index(("device", "packed-fallback"))
    halved = [i for i, a in enumerate(actions)
              if a == ("device", "retry-halved")]
    assert all(first_fb < i for i in halved)
    # The fault fires on every dispatch, so the ladder ends in the CPU
    # settle — the verdict must still be exact, never wrong.
    assert res.valid in (True, "unknown")
    monkeypatch.delenv(degrade.FAULT_ENV)
    cpu = check_wgl_cpu(packed, pm, time_limit_s=20.0)
    if res.valid != "unknown" and cpu.valid != "unknown":
        assert res.valid is cpu.valid


def test_witness_ladder_sheds_packing_first(monkeypatch):
    pm = cas_register().packed()
    h = random_register_history(400, procs=6, info_rate=0.02, seed=9)
    packed = pack_history(h, pm.encode)
    monkeypatch.setenv(degrade.FAULT_ENV, "witness")
    with degrade.capture() as steps:
        res = check_wgl_witness(packed, pm, packed_lanes=True,
                                time_limit_s=30.0)
    assert res is None  # witness failure only ever means escalate
    actions = [(s["tier"], s["action"]) for s in steps]
    assert ("witness", "packed-fallback") in actions


def test_batched_ladder_sheds_packing_first(monkeypatch):
    pm = cas_register().packed()
    packs = _register_trials(n_trials=4, procs=6)
    monkeypatch.setenv(degrade.FAULT_ENV, "batched")
    with degrade.capture() as steps:
        res = check_wgl_batched(packs, pm, packed_lanes=True,
                                time_limit_s=30.0)
    actions = [(s["tier"], s["action"]) for s in steps]
    assert ("batched", "packed-fallback") in actions
    # Persistent faulting ends in unknowns (the caller settles on CPU),
    # never a wrong verdict.
    assert all(v in (True, False, "unknown") for v in res.valid)


def test_packed_fallback_counter(monkeypatch):
    pm = cas_register().packed()
    h = random_register_history(120, procs=6, info_rate=0.05, seed=5)
    packed = pack_history(h, pm.encode)
    from jepsen_tpu import telemetry

    prev = telemetry.enabled()
    telemetry.enable(True)
    try:
        before = telemetry.counter_value("wgl.packed.fallbacks")
        monkeypatch.setenv(degrade.FAULT_ENV, "device")
        check_wgl_device(packed, pm, witness=False, packed_lanes=True,
                         time_limit_s=60.0)
        monkeypatch.delenv(degrade.FAULT_ENV)
        assert telemetry.counter_value("wgl.packed.fallbacks") > before
    finally:
        telemetry.enable(prev)


# -- columnar ingest fast path ---------------------------------------------


def test_append_many_byte_parity_fuzz():
    # Each side encodes with a fresh encoder, so the interner's codes
    # are compared too: they follow the order rows reach the encoder.
    def fresh():
        return cas_register().packed().encode

    rng = np.random.default_rng(29)
    for trial in range(13):
        if trial == 0:
            # Process 7 writes first and 0 writes next: interning in
            # process order instead of emit order gives other codes.
            h = history([
                Op(type="invoke", f="write", value=40, process=7),
                Op(type="invoke", f="write", value=41, process=0),
                Op(type="ok", f="write", value=40, process=7),
                Op(type="ok", f="write", value=41, process=0),
            ] + list(random_register_history(300, procs=5, seed=1)))
        else:
            h = random_register_history(
                int(rng.integers(1, 300)), procs=int(rng.integers(1, 7)),
                info_rate=float(rng.uniform(0, 0.3)),
                seed=int(rng.integers(0, 1 << 30)),
            )
        ops = list(h)
        ref = packed_to_bytes(pack_history(h, fresh()))
        scalar = PackedBuilder(fresh())
        for o in ops:
            scalar.append(o)
        assert packed_to_bytes(scalar.finish()) == ref
        whole = PackedBuilder(fresh())
        whole.append_many(ops)
        assert packed_to_bytes(whole.finish()) == ref, f"trial {trial}"
        # Random chunking, including tiny chunks (the scalar fallback)
        # and chunks that split invoke/completion pairs across calls.
        chunked = PackedBuilder(fresh())
        i = 0
        while i < len(ops):
            c = int(rng.integers(1, 80))
            chunked.append_many(ops[i:i + c])
            i += c
        assert packed_to_bytes(chunked.finish()) == ref, f"trial {trial}"


def test_append_many_snapshot_parity():
    pm = cas_register().packed()
    h = random_register_history(400, procs=5, info_rate=0.1, seed=31)
    ops = list(h)
    half = len(ops) // 2
    scalar = PackedBuilder(pm.encode)
    for o in ops[:half]:
        scalar.append(o)
    batched = PackedBuilder(pm.encode)
    batched.append_many(ops[:half])
    sp_s, bound_s = scalar.snapshot()
    sp_b, bound_b = batched.snapshot()
    assert bound_s == bound_b
    assert packed_to_bytes(sp_s) == packed_to_bytes(sp_b)
    for o in ops[half:]:
        scalar.append(o)
    batched.append_many(ops[half:])
    assert packed_to_bytes(scalar.finish()) == \
        packed_to_bytes(batched.finish())


def test_append_many_edge_pairings():
    """Double invokes, completion-without-invocation, FAIL drops, and
    nemesis noise — the state-machine edges of the pairing rewrite."""
    pm = cas_register().packed()
    ops = [
        Op(type="invoke", f="write", value=1, process=0),
        Op(type="invoke", f="write", value=9, process="nemesis"),  # noise
        # Double invoke: the first write becomes indeterminate.
        Op(type="invoke", f="write", value=2, process=0),
        Op(type="ok", f="write", value=2, process=0),
        # Completion with no invocation: tolerated, dropped.
        Op(type="ok", f="write", value=3, process=1),
        Op(type="invoke", f="write", value=4, process=1),
        Op(type="fail", f="write", value=4, process=1),  # dropped
        Op(type="invoke", f="read", value=None, process=2),  # unfinished
    ]
    h = history(ops)
    ref = packed_to_bytes(pack_history(h, pm.encode))
    b = PackedBuilder(pm.encode)
    b.append_many(list(h))
    assert packed_to_bytes(b.finish()) == ref
    # Same ops split so the double invoke straddles a chunk boundary
    # (carried-pending interaction) — and force the numpy path by
    # padding each side past the scalar-fallback threshold with
    # nemesis noise (non-client ops never consume event indices).
    pad = [Op(type="invoke", f="noise", value=None, process="nemesis")
           ] * PackedBuilder._MANY_MIN
    b2 = PackedBuilder(pm.encode)
    b2.append_many(list(h)[:2] + pad)
    b2.append_many(pad + list(h)[2:])
    assert packed_to_bytes(b2.finish()) == ref


def test_append_many_int32_overflow_guard():
    # a0/a1 past int32 must still bail loudly through the columnar path.
    def encode(inv, comp):
        return (0, 2 ** 31, 0)

    b = PackedBuilder(encode)
    ops = []
    for i in range(40):
        ops.append(Op(type="invoke", f="write", value=1, process=i % 4))
        ops.append(Op(type="ok", f="write", value=1, process=i % 4))
    b.append_many(list(history(ops)))
    with pytest.raises(OverflowError):
        b.finish()
