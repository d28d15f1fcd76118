"""Batched per-key Wing–Gong–Lowe search over a TPU mesh.

This is the TPU-native re-design of `jepsen.independent`'s checker
(/root/reference/jepsen/src/jepsen/independent.clj:327-377): where the
reference runs knossos once per key under a `bounded-pmap` of JVM
threads, here every key's search *is the batch axis* — K independent
histories are padded to a common shape, the WGL frontier search runs
vmapped over keys on one device, and `shard_map` splits the key axis
across the mesh so each device advances its own keys with no
cross-device chatter (per-key searches are embarrassingly parallel; the
collectives-free inner loop rides entirely in VMEM/HBM).

Unlike ops/wgl.py (single giant history, windowed frontier), per-key
histories are short by construction — the reference bounds them
precisely because knossos explodes otherwise
(tests/linearizable_register.clj:39-53) — so the whole history fits in
the member bitset and no windowing is needed.

Soundness contract (same as ops/wgl.py): `accepted` verdicts are always
sound (a witness linearization was found).  `invalid` is only reported
when the search was exact (no beam/candidate overflow); overflow
degrades to "unknown", which the host settles with the exact CPU search.
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import Any, Optional

import numpy as np

from .. import telemetry
from ..telemetry import profile, roofline
from ..history.packed import ST_OK, PackedOps
from ..models.base import PackedModel
from . import degrade, packing
from .wgl import packed_enabled

INF = np.int32(2**31 - 1)

_kernel_cache: dict[tuple, Any] = {}


def _hash_vectors(n: int, sw: int, seed: int = 0x5EED) -> tuple[np.ndarray, ...]:
    rng = np.random.default_rng(seed)
    return (
        rng.uniform(1.0, 2.0, size=(n,)).astype(np.float32),
        rng.uniform(1.0, 2.0, size=(n,)).astype(np.float32),
        rng.uniform(1.0, 2.0, size=(sw,)).astype(np.float32),
        rng.uniform(1.0, 2.0, size=(sw,)).astype(np.float32),
    )


def _bucket(x: int, lo: int = 32) -> int:
    w = lo
    while w < x:
        w *= 2
    return w


@dataclass
class BatchedPack:
    """K per-key histories padded to a common (K, N) table."""

    ret: np.ndarray  # (K, N) int32, INF for info/padding
    inv: np.ndarray  # (K, N) int32, INF for padding
    f: np.ndarray    # (K, N) int32
    a0: np.ndarray   # (K, N) int32
    a1: np.ndarray   # (K, N) int32
    okv: np.ndarray  # (K, N) bool
    n_ops: np.ndarray  # (K,) int32 live op count per key
    keys: list = field(default_factory=list)

    @property
    def K(self) -> int:
        return int(self.ret.shape[0])

    @property
    def N(self) -> int:
        return int(self.ret.shape[1])


def pack_batch(packs: list[PackedOps], pad_keys_to: Optional[int] = None) -> BatchedPack:
    """Stacks per-key PackedOps into padded (K, N) arrays.  Padding ops
    have inv = ret = INF so they are never order-legal candidates and
    never block anyone; padding *keys* (to fill a mesh) have n_ops = 0
    and accept immediately."""
    K = len(packs)
    Kp = pad_keys_to if pad_keys_to and pad_keys_to > K else K
    N = _bucket(max((p.n for p in packs), default=1))
    ret = np.full((Kp, N), INF, dtype=np.int32)
    inv = np.full((Kp, N), INF, dtype=np.int32)
    f = np.zeros((Kp, N), dtype=np.int32)
    a0 = np.zeros((Kp, N), dtype=np.int32)
    a1 = np.zeros((Kp, N), dtype=np.int32)
    okv = np.zeros((Kp, N), dtype=bool)
    n_ops = np.zeros(Kp, dtype=np.int32)
    for k, p in enumerate(packs):
        n = p.n
        n_ops[k] = n
        if n == 0:
            continue
        inv[k, :n] = p.inv.astype(np.int64).clip(max=int(INF) - 1)
        ret[k, :n] = p.ret.clip(max=int(INF)).astype(np.int64)
        f[k, :n] = p.f
        a0[k, :n] = p.a0
        a1[k, :n] = p.a1
        okv[k, :n] = p.status == ST_OK
    return BatchedPack(ret=ret, inv=inv, f=f, a0=a0, a1=a1, okv=okv, n_ops=n_ops)


def _make_key_fn(B: int, N: int, SW: int, Cmax: int, jax_step,
                 packed: bool = False):
    """One key's full frontier search: (tables…) -> (accepted, alive_end,
    incomplete, explored).  vmap'd over the key axis by the caller.

    With `packed`, the member/child bitsets ride as ceil(N/32) uint32
    lanes between levels (ops/packing.py): word-OR children, packed
    cover test, wrapping-uint32 dedup hashes.  Under the caller's vmap
    the level advances every key's frontier in one dispatch, so the
    unpack + candidate rule is one (K*B, N) operand and the dedup hash
    one (K*Cmax, Np) integer contraction — the batched, matmul-shaped
    step the wide engine only approximates with bool tensors."""
    import jax
    import jax.numpy as jnp

    if packed:
        Np = packing.n_words(N)
        hw1 = jnp.asarray(packing.hash_consts(Np, 0))
        hw2 = jnp.asarray(packing.hash_consts(Np, 1))
        shw1 = jnp.asarray(packing.hash_consts(SW, 2))
        shw2 = jnp.asarray(packing.hash_consts(SW, 3))
    else:
        h1v, h2v, sh1v, sh2v = (
            jnp.asarray(v) for v in _hash_vectors(N, SW)
        )

    def level_step(carry, tables):
        member, states, alive, accepted, incomplete, explored, it = carry
        ret, inv, f, a0, a1, okv, init_state, n_ops = tables
        member_w = member
        if packed:
            member = packing.unpack_bits(member_w, N)

        # Candidate rule: a non-member a may be linearized next iff
        # inv(a) < min ret over the *other* non-members — two masked
        # min-reductions per config (see ops/wgl.py).
        nm_ret = jnp.where(member | ~alive[:, None], INF, ret[None, :])  # (B, N)
        m1 = nm_ret.min(axis=1)
        am1 = jnp.argmin(nm_ret, axis=1)
        nm_ret2 = nm_ret.at[jnp.arange(B), am1].set(INF)
        m2 = nm_ret2.min(axis=1)
        bound = jnp.where(
            jnp.arange(N)[None, :] == am1[:, None], m2[:, None], m1[:, None]
        )
        order_ok = (~member) & alive[:, None] & (inv[None, :] < bound)

        # Compact candidate (config, op) pairs.
        flat = order_ok.reshape(-1)
        count = flat.sum()
        cand_idx = jnp.nonzero(flat, size=Cmax, fill_value=0)[0]
        valid_c = jnp.arange(Cmax) < count
        incomplete = incomplete | (count > Cmax)
        parent = cand_idx // N
        a = cand_idx % N

        # Model transition over survivors.
        new_states, legal = jax.vmap(jax_step)(states[parent], f[a], a0[a], a1[a])
        live_c = valid_c & legal
        if packed:
            # Packed child: word-OR the parent lanes + one hot bit;
            # cover test and dedup hashes run on the uint32 words
            # (okv arrives pre-packed from key_fn).
            child = packing.set_bit(member_w[parent], a)
            cover = packing.covers(child, okv)
            accepted = accepted | jnp.any(live_c & cover)
            su = packing.as_u32(new_states)
            dead = jnp.uint32(0xFFFFFFFF)
            h1 = jnp.where(
                live_c,
                packing.hash_words(child, hw1)
                + packing.hash_words(su, shw1),
                dead,
            )
            h2 = jnp.where(
                live_c,
                packing.hash_words(child, hw2)
                + packing.hash_words(su, shw2),
                dead,
            )
        else:
            child = member[parent].at[jnp.arange(Cmax), a].set(True)

            # Accept when some live child covers every :ok op.
            cover = (child | ~okv[None, :]).all(axis=1)
            accepted = accepted | jnp.any(live_c & cover)

            # Dedup via float-hash sort + exact adjacent compare.
            cf = child.astype(jnp.float32)
            sf = new_states.astype(jnp.float32)
            big = jnp.float32(3.0e38)
            h1 = jnp.where(live_c, cf @ h1v + sf @ sh1v, big)
            h2 = jnp.where(live_c, cf @ h2v + sf @ sh2v, big)
        h1s, h2s, perm = jax.lax.sort((h1, h2, jnp.arange(Cmax)), num_keys=2)
        child_s = child[perm]
        states_s = new_states[perm]
        live_s = live_c[perm]
        same_h = (h1s == jnp.roll(h1s, 1)) & (h2s == jnp.roll(h2s, 1))
        same_h = same_h.at[0].set(False)
        same_full = (
            same_h
            & (child_s == jnp.roll(child_s, 1, axis=0)).all(axis=1)
            & (states_s == jnp.roll(states_s, 1, axis=0)).all(axis=1)
        )
        uniq = live_s & ~same_full
        n_uniq = uniq.sum()
        incomplete = incomplete | (n_uniq > B)

        sel = jnp.nonzero(uniq, size=B, fill_value=0)[0]
        new_alive = jnp.arange(B) < jnp.minimum(n_uniq, B)
        return (
            child_s[sel],
            states_s[sel],
            new_alive,
            accepted,
            incomplete,
            explored + jnp.minimum(n_uniq, B),
            it + 1,
        )

    def key_fn(ret, inv, f, a0, a1, okv, init_state, n_ops):
        if packed:
            member0 = jnp.zeros((B, Np), dtype=jnp.uint32)
        else:
            member0 = jnp.zeros((B, N), dtype=bool)
        states0 = jnp.tile(init_state[None, :], (B, 1))
        alive0 = jnp.arange(B) < 1
        accepted0 = ~okv.any()
        ok_t = packing.pack_bits(okv, Np) if packed else okv
        tables = (ret, inv, f, a0, a1, ok_t, init_state, n_ops)

        def cond(carry):
            _, _, alive, accepted, _, _, it = carry
            return (~accepted) & jnp.any(alive) & (it < n_ops)

        def body(carry):
            return level_step(carry, tables)

        carry = (
            member0,
            states0,
            alive0,
            accepted0,
            jnp.bool_(False),
            jnp.int32(0),
            jnp.int32(0),
        )
        member, states, alive, accepted, incomplete, explored, it = (
            jax.lax.while_loop(cond, body, carry)
        )
        return accepted, jnp.any(alive), incomplete, explored

    return key_fn


def _get_kernel(B: int, N: int, SW: int, Cmax: int, jax_step, mesh=None,
                packed: bool = False):
    """The jitted batched kernel: vmap over keys, shard_map over the mesh
    'keys' axis when a mesh is given (each device runs its slice of keys
    independently — no collectives in the hot loop)."""
    import jax

    # Strong-reference keys: id() collides after GC address reuse.
    key = (B, N, SW, Cmax, jax_step, mesh, packed)
    fn = _kernel_cache.get(key)
    if fn is not None:
        return fn

    key_fn = _make_key_fn(B, N, SW, Cmax, jax_step, packed=packed)
    batched = jax.vmap(key_fn, in_axes=(0, 0, 0, 0, 0, 0, None, 0))
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        pk = P("keys")
        in_specs = (pk, pk, pk, pk, pk, pk, P(None), pk)
        out_specs = (pk, pk, pk, pk)
        batched = jax.shard_map(
            batched, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
    fn = roofline.instrument(jax.jit(batched))
    _kernel_cache[key] = fn
    return fn


@dataclass
class BatchedWGLResult:
    #: per-key verdicts: True | False | "unknown" (pre-CPU-fallback)
    valid: list
    explored: np.ndarray
    elapsed_s: float
    beam_used: int


def check_wgl_batched(
    packs: list[PackedOps],
    pm: PackedModel,
    *,
    beam: int = 256,
    max_beam: int = 16384,
    cand_factor: int = 4,
    mesh=None,
    time_limit_s: Optional[float] = None,
    packed_lanes: Optional[bool] = None,
) -> BatchedWGLResult:
    """Runs the WGL search for every key at once on device.  Keys whose
    search overflowed the beam are retried together with a doubled beam;
    at max_beam survivors report "unknown" (the caller settles them on
    CPU).  The time limit is checked between beam-retry rounds (the
    device block itself is uninterruptible); unsettled keys at the
    deadline report "unknown"."""
    import jax.numpy as jnp

    t0 = time.monotonic()
    K = len(packs)
    n_dev = math.prod(mesh.devices.shape) if mesh is not None else 1
    pad_keys = max(K, n_dev) if mesh is None else n_dev * math.ceil(K / n_dev)
    bp = pack_batch(packs, pad_keys_to=pad_keys)
    SW = pm.state_width
    init_state = np.asarray(pm.init_state, dtype=np.int32)

    verdict: list[Any] = [None] * K
    explored = np.zeros(K, dtype=np.int64)
    todo = list(range(K))
    B = _bucket(beam, lo=32)
    packed_on = packed_enabled(packed_lanes)
    batch_retried = False  # one halved-beam retry on resource errors

    # One cost record per batched pass: shape features, the beam plan,
    # and the compile/execute split folded in from the span hook.
    with profile.capture(
        "batched", keys=K, ops=int(sum(p.n for p in packs)),
    ) as _pb:
        _pb.knob(beam=B, max_beam=int(max_beam),
                 cand_factor=int(cand_factor), mesh=mesh is not None,
                 packed=packed_on)
        while todo:
            if mesh is not None:
                pad_t = n_dev * math.ceil(len(todo) / n_dev)
            else:
                pad_t = len(todo)
            sel = np.asarray(todo + [todo[0]] * (pad_t - len(todo)))
            # jax.jit is lazy: a cache-miss kernel pays trace+compile inside
            # its first call, so the span name splits compile vs execute
            # exactly like the witness/BFS tiers (the phase profile and the
            # per-pass cost record both read this convention).
            fresh_fn = (B, bp.N, SW, cand_factor * B, pm.jax_step,
                        mesh, packed_on) not in _kernel_cache
            fn = _get_kernel(B, bp.N, SW, cand_factor * B, pm.jax_step,
                             mesh, packed=packed_on)
            if packed_on and telemetry.enabled():
                telemetry.count("wgl.packed.batched-rounds")
            sp = telemetry.span(
                "wgl.batched.compile" if fresh_fn else "wgl.batched.block",
                keys=len(todo), beam=B,
            ) if telemetry.enabled() else telemetry.span("")
            try:
                degrade.maybe_fault("batched")
                with sp:
                    acc, alive_end, inc, expl = fn(
                        jnp.asarray(bp.ret[sel]),
                        jnp.asarray(bp.inv[sel]),
                        jnp.asarray(bp.f[sel]),
                        jnp.asarray(bp.a0[sel]),
                        jnp.asarray(bp.a1[sel]),
                        jnp.asarray(bp.okv[sel]),
                        jnp.asarray(init_state),
                        jnp.asarray(bp.n_ops[sel]),
                    )
                    # The host transfers stay inside the try: jitted
                    # dispatch is asynchronous, so execution failures raise
                    # at consumption.
                    acc = np.asarray(acc)
                    alive_end = np.asarray(alive_end)
                    inc = np.asarray(inc)
                    expl = np.asarray(expl)
            except Exception as e:  # noqa: BLE001
                if not degrade.is_resource_error(e):
                    raise
                # Degradation ladder: evict the compiled kernel, retry ONCE
                # with a halved beam (and cap the overflow ladder there so
                # it can't climb back into the OOM region); a second
                # failure hands every unsettled key to the CPU settle.
                _kernel_cache.pop(
                    (B, bp.N, SW, cand_factor * B, pm.jax_step, mesh,
                     packed_on), None
                )
                if packed_on:
                    # First rung: shed the packed lanes at the SAME beam
                    # before surrendering any width (see ops/wgl.py).
                    packed_on = False
                    degrade.record("batched", "packed-fallback", e)
                    telemetry.count("wgl.packed.fallbacks")
                    continue
                if batch_retried or B <= 32:
                    degrade.record("batched", "fall-through", e)
                    for k in todo:
                        verdict[k] = "unknown"
                    todo = []
                    continue
                batch_retried = True
                degrade.record("batched", "retry-halved", e)
                B //= 2
                max_beam = min(max_beam, B)
                continue

            retry = []
            for i, k in enumerate(todo):
                explored[k] += int(expl[i])
                if acc[i]:
                    verdict[k] = True
                elif inc[i]:
                    # Inexact (beam/candidate overflow): a wider beam can
                    # genuinely settle it.
                    if B < max_beam:
                        retry.append(k)
                    else:
                        verdict[k] = "unknown"
                elif alive_end[i]:
                    # Defensive guard: an exact search ended with a live
                    # frontier but no acceptance, which shouldn't happen —
                    # re-running with a wider beam can't change an exact
                    # outcome, so don't ride the ladder (round-1 weak #5:
                    # each rung recompiles); report unknown for the CPU
                    # fallback to settle.
                    verdict[k] = "unknown"
                else:
                    verdict[k] = False  # exact search exhausted: invalid
            todo = retry
            if todo:
                if time_limit_s is not None and time.monotonic() - t0 > time_limit_s:
                    for k in todo:
                        verdict[k] = "unknown"
                    todo = []
                else:
                    B *= 2

        _pb.outcome = {
            "proven": sum(1 for v in verdict if v is True),
            "refuted": sum(1 for v in verdict if v is False),
            "unknown": sum(1 for v in verdict if v == "unknown"),
        }
        _pb.degraded = batch_retried or None
    if telemetry.enabled():
        # Tier populations for the cohort-settle ladder: an exact False
        # here is a device REFUTATION the settle tier can accept
        # without an exhaustive CPU search (soundness contract above).
        telemetry.count("wgl.batched.keys", K)
        telemetry.count("wgl.batched.proven",
                        sum(1 for v in verdict if v is True))
        telemetry.count("wgl.batched.refuted",
                        sum(1 for v in verdict if v is False))
        telemetry.count("wgl.batched.unknown",
                        sum(1 for v in verdict if v == "unknown"))
    return BatchedWGLResult(
        valid=verdict,
        explored=explored,
        elapsed_s=time.monotonic() - t0,
        beam_used=B,
    )
