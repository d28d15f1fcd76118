"""Device (TPU) Wing–Gong–Lowe linearizability search.

The BASELINE.json north star: knossos's sequential WGL DFS becomes a
batched breadth-first frontier search over configurations, JIT-compiled
and vmapped on device.  See checker/wgl_cpu.py for the shared formulation;
this module is the SIMD re-design, not a port (SURVEY.md §7 stage 3):

* BFS by linearized-count level: every frontier config has |S| = n, so the
  member-set needs bits only for the *active window* — ops that are
  neither guaranteed-members (horizon < n, must be linearized by level n
  in any valid prefix) nor guaranteed-non-members (preds ≥ n + K, can't be
  linearized within this block of K levels).  The window is recomputed on
  host every K levels and the frontier re-gathered; window size tracks the
  history's concurrency + accumulated indeterminate (:info) ops, not its
  length.
* The candidate rule (op a appendable iff inv(a) < min ret over other
  non-members) becomes two masked min-reductions per config — no per-op
  predecessor masks, no (B, W, W) intermediates.
* Candidate (config, op) pairs are compacted with a static-size nonzero,
  the model transition (models/base.py jax_step) is vmapped over the
  survivors, and children are deduplicated by float-hash sort + exact
  adjacent compare — equal configs always hash equal, so dedup is exact;
  hash collisions only cost beam slots.
* Beam/candidate overflow is detected on device; the host retries the
  block with a doubled beam (frontier state is re-gathered from the block
  start), so completeness is only surrendered at max_beam, where the
  verdict degrades from invalid to :unknown (valid stays sound).

Per-key independent histories batch along a leading axis and shard across
the TPU mesh (parallel/independent.py), turning `jepsen.independent`'s
bounded-pmap (independent.clj:327-377) into data parallelism over devices.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Any, Optional

import numpy as np

from .. import telemetry
from ..telemetry import profile, roofline
from ..checker.wgl_cpu import WGLResult
from ..history.packed import ST_OK, PackedOps
from ..models.base import PackedModel
from . import degrade, packing

INF = np.int32(2**31 - 1)

#: JEPSEN_WGL_PACKED=0 disables the uint32 bit-packed member lanes and
#: falls back to the wide bool (B, W) tensors everywhere.
PACKED_ENV = "JEPSEN_WGL_PACKED"

_block_fn_cache: dict[tuple, Any] = {}


def packed_enabled(packed_lanes: Optional[bool] = None) -> bool:
    """Resolve the packed-lane switch: explicit arg wins, then the
    JEPSEN_WGL_PACKED env (default on)."""
    import os

    if packed_lanes is not None:
        return bool(packed_lanes)
    return os.environ.get(PACKED_ENV, "1") not in ("0", "false", "off")


def _hash_vectors(w: int, sw: int, seed: int = 0x5EED) -> tuple[np.ndarray, ...]:
    rng = np.random.default_rng(seed)
    return (
        rng.uniform(1.0, 2.0, size=(w,)).astype(np.float32),
        rng.uniform(1.0, 2.0, size=(w,)).astype(np.float32),
        rng.uniform(1.0, 2.0, size=(sw,)).astype(np.float32),
        rng.uniform(1.0, 2.0, size=(sw,)).astype(np.float32),
    )



def _expand_level(member, states, alive, tables, n_rows, n_slots,
                  jax_step):
    """One frontier level's expansion, shared by the single-device and
    frontier-sharded block fns: candidate rule (two masked
    min-reductions per config), static-size compaction, vmapped model
    step, child bitsets, acceptance and dedup hashes.  `n_rows` is the
    (local) frontier height, `n_slots` the (local) candidate budget.

    Returns (child, new_states, live_c, h1, h2, accepted_any,
    overflow)."""
    import jax
    import jax.numpy as jnp

    (ret_w, inv_w, f_w, a0_w, a1_w, ok_w, fmin1, f_has_ok,
     h1v, h2v, sh1v, sh2v) = tables
    W = ret_w.shape[0]

    # --- candidate rule ---------------------------------------------
    nm_ret = jnp.where(member | ~alive[:, None], INF, ret_w[None, :])
    m1w = nm_ret.min(axis=1)
    am1 = jnp.argmin(nm_ret, axis=1)
    nm_ret2 = nm_ret.at[jnp.arange(n_rows), am1].set(INF)
    m2w = nm_ret2.min(axis=1)
    # Merge with the (host-precomputed) min over "future" ops outside
    # the window — they are non-members of every config.
    is_w_min = m1w <= fmin1
    total_m1 = jnp.minimum(m1w, fmin1)
    second_for_argmin = jnp.minimum(m2w, fmin1)
    bound = jnp.where(
        (jnp.arange(W)[None, :] == am1[:, None]) & is_w_min[:, None],
        second_for_argmin[:, None],
        total_m1[:, None],
    )
    order_ok = (~member) & alive[:, None] & (inv_w[None, :] < bound)

    # --- compact candidate (config, op) pairs ------------------------
    flat = order_ok.reshape(-1)
    count = flat.sum()
    cand_idx = jnp.nonzero(flat, size=n_slots, fill_value=0)[0]
    valid_c = jnp.arange(n_slots) < count
    overflow = count > n_slots
    parent = cand_idx // W
    a = cand_idx % W

    # --- model transition, vmapped over survivors only ---------------
    new_states, legal = jax.vmap(jax_step)(
        states[parent], f_w[a], a0_w[a], a1_w[a]
    )
    live_c = valid_c & legal

    child = member[parent]
    child = child.at[jnp.arange(n_slots), a].set(True)

    # --- acceptance: some live child covers every :ok op -------------
    cover = (child | ~ok_w[None, :]).all(axis=1)
    accepted_any = jnp.any(live_c & cover & ~f_has_ok)

    # --- dedup hashes ------------------------------------------------
    cf = child.astype(jnp.float32)
    sf = new_states.astype(jnp.float32)
    big = jnp.float32(3.0e38)
    h1 = jnp.where(live_c, cf @ h1v + sf @ sh1v, big)
    h2 = jnp.where(live_c, cf @ h2v + sf @ sh2v, big)
    return child, new_states, live_c, h1, h2, accepted_any, overflow


def _expand_level_packed(member_w, states, alive, tables, n_rows,
                         n_slots, jax_step):
    """Bit-packed twin of _expand_level: the frontier member sets ride
    as uint32 lanes (W bools -> ceil(W/32) words), children are built
    with word-OR + one hot bit, acceptance is a packed cover test, and
    the dedup hashes are wrapping uint32 multiply-adds over the words.
    The candidate rule still needs per-slot ints, so the member bits
    are unpacked once per level — everything carried between levels
    (and gathered over ICI in the sharded path) stays packed."""
    import jax
    import jax.numpy as jnp

    (ret_w, inv_w, f_w, a0_w, a1_w, ok_words, fmin1, f_has_ok,
     hw1, hw2, shw1, shw2) = tables
    W = ret_w.shape[0]
    member = packing.unpack_bits(member_w, W)

    # --- candidate rule (identical to the wide engine) ---------------
    nm_ret = jnp.where(member | ~alive[:, None], INF, ret_w[None, :])
    m1w = nm_ret.min(axis=1)
    am1 = jnp.argmin(nm_ret, axis=1)
    nm_ret2 = nm_ret.at[jnp.arange(n_rows), am1].set(INF)
    m2w = nm_ret2.min(axis=1)
    is_w_min = m1w <= fmin1
    total_m1 = jnp.minimum(m1w, fmin1)
    second_for_argmin = jnp.minimum(m2w, fmin1)
    bound = jnp.where(
        (jnp.arange(W)[None, :] == am1[:, None]) & is_w_min[:, None],
        second_for_argmin[:, None],
        total_m1[:, None],
    )
    order_ok = (~member) & alive[:, None] & (inv_w[None, :] < bound)

    flat = order_ok.reshape(-1)
    count = flat.sum()
    cand_idx = jnp.nonzero(flat, size=n_slots, fill_value=0)[0]
    valid_c = jnp.arange(n_slots) < count
    overflow = count > n_slots
    parent = cand_idx // W
    a = cand_idx % W

    new_states, legal = jax.vmap(jax_step)(
        states[parent], f_w[a], a0_w[a], a1_w[a]
    )
    live_c = valid_c & legal

    child_w = packing.set_bit(member_w[parent], a)

    # --- acceptance: packed cover over the ok-mask words -------------
    cover = packing.covers(child_w, ok_words)
    accepted_any = jnp.any(live_c & cover & ~f_has_ok)

    # --- dedup hashes: uint32 wrap-sum over words + states -----------
    su = packing.as_u32(new_states)
    dead = jnp.uint32(0xFFFFFFFF)
    h1 = jnp.where(
        live_c,
        packing.hash_words(child_w, hw1) + packing.hash_words(su, shw1),
        dead,
    )
    h2 = jnp.where(
        live_c,
        packing.hash_words(child_w, hw2) + packing.hash_words(su, shw2),
        dead,
    )
    return child_w, new_states, live_c, h1, h2, accepted_any, overflow


def _dedup_sort(child, new_states, live_c, h1, h2, n_slots):
    """Hash-sort + exact adjacent compare over candidates: equal
    configs always hash equal, so dedup is exact; collisions only cost
    slots.  Returns (child_s, states_s, uniq, n_uniq) in sort order."""
    import jax
    import jax.numpy as jnp

    h1s, h2s, perm = jax.lax.sort(
        (h1, h2, jnp.arange(n_slots)), num_keys=2
    )
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
    return child_s, states_s, uniq, uniq.sum()


def _make_block_fn(B: int, W: int, SW: int, Cmax: int, jax_step,
                   packed: bool = False):
    """Builds the jitted block runner for static shapes (B, W, SW, Cmax).

    Carry: member (B, W) bool — or (B, ceil(W/32)) uint32 when
    `packed` — states (B, SW) i32, alive (B,) bool, accepted,
    incomplete (bool), explored (i32), it (i32).
    """
    import jax
    import jax.numpy as jnp

    expand = _expand_level_packed if packed else _expand_level

    def level_step(carry, tables):
        member, states, alive, accepted, incomplete, explored, it = carry
        child, new_states, live_c, h1, h2, acc, overflow = expand(
            member, states, alive, tables, B, Cmax, jax_step
        )
        accepted = accepted | acc
        incomplete = incomplete | overflow
        child_s, states_s, uniq, n_uniq = _dedup_sort(
            child, new_states, live_c, h1, h2, Cmax
        )
        incomplete = incomplete | (n_uniq > B)

        # --- select the next frontier ------------------------------------
        sel = jnp.nonzero(uniq, size=B, fill_value=0)[0]
        new_alive = jnp.arange(B) < jnp.minimum(n_uniq, B)
        new_member = child_s[sel]
        new_states_f = states_s[sel]
        explored = explored + jnp.minimum(n_uniq, B)
        return (
            new_member,
            new_states_f,
            new_alive,
            accepted,
            incomplete,
            explored,
            it + 1,
        )

    def block(member, states, alive, iters, *tables):
        def cond(carry):
            _, _, alive, accepted, _, _, it = carry
            return (~accepted) & jnp.any(alive) & (it < iters)

        def body(carry):
            return level_step(carry, tables)

        carry = (
            member,
            states,
            alive,
            jnp.bool_(False),
            jnp.bool_(False),
            jnp.int32(0),
            jnp.int32(0),
        )
        return jax.lax.while_loop(cond, body, carry)

    return roofline.instrument(jax.jit(block))


def _make_block_fn_sharded(B: int, W: int, SW: int, Cmax: int, jax_step,
                           mesh, packed: bool = False):
    """Frontier-sharded variant of _make_block_fn: ONE search's beam
    splits across the mesh (the within-search axis SURVEY.md §5 frames
    as the ring-attention analog — parallelism over the configuration
    frontier rather than over sequence position).

    Layout per level: the B frontier rows and their candidate
    expansion (the FLOP-heavy part: candidate rule over (B, W),
    Cmax model steps, (Cmax, W) child bitsets) are sharded B/n per
    device; candidates then `all_gather` over ICI (hashes + bitsets +
    states) and the small global dedup-sort runs replicated, after
    which each device keeps its B/n slice of the new frontier.
    Verdict-relevant scalars (accepted / incomplete / n_alive) are
    globalized with `psum`, so control flow stays identical on every
    device.  Verdicts match the single-device search exactly; the one
    behavioral difference is overflow detection — candidate compaction
    is per-shard (Cmax/n slots each), so a lopsided level can trip the
    (sound) beam-retry/unknown path where the global compactor would
    not."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]
    n = mesh.devices.size
    assert B % n == 0 and Cmax % n == 0, (B, Cmax, n)
    B_l = B // n
    C_l = Cmax // n
    expand = _expand_level_packed if packed else _expand_level

    def level_step(carry, tables):
        (member, states, alive, accepted, incomplete, explored, it,
         n_alive) = carry

        # --- expansion on the LOCAL frontier rows -----------------------
        # With packed lanes the all_gather below moves uint32 words —
        # 8x fewer ICI bytes per candidate bitset than the bool rows.
        child, new_states, live_c, h1, h2, acc_local, local_overflow = (
            expand(
                member, states, alive, tables, B_l, C_l, jax_step
            )
        )

        # --- globalize: gather candidates, psum flags -------------------
        def gather(x):
            return jax.lax.all_gather(x, axis).reshape(
                (Cmax,) + x.shape[1:]
            )

        child_g = gather(child)
        states_g = gather(new_states)
        live_g = gather(live_c)
        h1_g = gather(h1)
        h2_g = gather(h2)
        accepted = accepted | (
            jax.lax.psum(acc_local.astype(jnp.int32), axis) > 0
        )
        incomplete = incomplete | (
            jax.lax.psum(local_overflow.astype(jnp.int32), axis) > 0
        )

        # --- replicated dedup-sort over the gathered candidates ---------
        child_s, states_s, uniq, n_uniq = _dedup_sort(
            child_g, states_g, live_g, h1_g, h2_g, Cmax
        )
        incomplete = incomplete | (n_uniq > B)

        # --- each device keeps its slice of the new frontier ------------
        sel = jnp.nonzero(uniq, size=B, fill_value=0)[0]
        d = jax.lax.axis_index(axis)
        sel_l = jax.lax.dynamic_slice_in_dim(sel, d * B_l, B_l)
        n_alive = jnp.minimum(n_uniq, B)
        new_alive = (jnp.arange(B_l) + d * B_l) < n_alive
        new_member = child_s[sel_l]
        new_states_f = states_s[sel_l]
        explored = explored + n_alive
        return (
            new_member, new_states_f, new_alive,
            accepted, incomplete, explored, it + 1, n_alive,
        )

    def block_local(member, states, alive, iters, *tables):
        def cond(carry):
            _, _, _, accepted, _, _, it, n_alive = carry
            return (~accepted) & (n_alive > 0) & (it < iters)

        def body(carry):
            return level_step(carry, tables)

        n_alive0 = jax.lax.psum(alive.sum(), axis)
        carry = (
            member, states, alive,
            jnp.bool_(False), jnp.bool_(False),
            jnp.int32(0), jnp.int32(0), n_alive0,
        )
        out = jax.lax.while_loop(cond, body, carry)
        return out[:7]  # drop the internal n_alive

    pb = P(axis)
    pr = P()
    sharded = jax.shard_map(
        block_local, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), pb, pr) + (pr,) * 12,
        out_specs=(P(axis, None), P(axis, None), pb, pr, pr, pr, pr),
        check_vma=False,
    )
    return roofline.instrument(jax.jit(sharded))


def _bucket(x: int, lo: int = 256) -> int:
    w = lo
    while w < x:
        w *= 2
    return w


def window_regather(prev_active: np.ndarray, active: np.ndarray):
    """(perm, present) mapping a new window layout onto the previous
    one: new column j reads old column perm[j] where present[j].  Shared
    by the BFS and witness paths so boundary handling stays in one
    place."""
    pos = np.searchsorted(prev_active, active)
    pos_clip = np.clip(pos, 0, len(prev_active) - 1)
    present = (pos < len(prev_active)) & (prev_active[pos_clip] == active)
    perm = np.where(present, pos_clip, 0)
    return perm, present


def _window_tables(packed: PackedOps, n0: int, K: int, max_window: int):
    """Host-side window computation for levels [n0, n0+K)."""
    preds = packed.preds
    horizon = packed.horizon
    active = np.nonzero((preds < n0 + K) & (horizon >= n0))[0]
    if len(active) > max_window:
        return None  # window overflow
    future = np.nonzero(preds >= n0 + K)[0]
    ret = np.minimum(packed.ret, np.int64(INF)).astype(np.int32)
    if len(future):
        fr = np.sort(ret[future])
        fmin1 = np.int32(fr[0])
        f_has_ok = bool((packed.status[future] == ST_OK).any())
    else:
        fmin1 = INF
        f_has_ok = False
    W = _bucket(max(len(active), 1))
    pad = W - len(active)

    def pad_to(arr, fill):
        return np.concatenate([arr, np.full(pad, fill, dtype=arr.dtype)])

    tables = dict(
        ret_w=pad_to(ret[active], INF),
        inv_w=pad_to(packed.inv[active].astype(np.int32), INF),
        f_w=pad_to(packed.f[active], 0),
        a0_w=pad_to(packed.a0[active], 0),
        a1_w=pad_to(packed.a1[active], 0),
        ok_w=pad_to(packed.status[active] == ST_OK, False),
        fmin1=fmin1,
        f_has_ok=np.bool_(f_has_ok),
    )
    return active, W, tables


def check_wgl_device(
    packed: PackedOps,
    pm: PackedModel,
    *,
    beam: int = 1024,
    max_beam: int = 4096,
    block: int = 256,
    cand_factor: int = 4,
    max_window: int = 16384,
    time_limit_s: Optional[float] = None,
    witness: bool = True,
    width_hint: int = 0,
    mesh: Any = None,
    checkpoint_dir: Optional[str] = None,
    packed_lanes: Optional[bool] = None,
) -> WGLResult:
    """Decides linearizability of one packed history on the default JAX
    device.

    Two tiers: first the just-in-time witness search
    (ops/wgl_witness.py) — exact for valid verdicts and immune to the
    high-:info frontier explosion; if it finds no witness, the exhaustive
    frontier BFS below settles invalid.  The BFS is exact until
    `max_beam`/`max_window` overflow, after which invalid degrades to
    "unknown" (valid verdicts remain sound).  `max_beam` defaults low:
    beyond ~4096 the ladder's recompiles and frontier costs exceed the
    CPU fallback's (round-1 measurement: 65536 hung >280 s where 4096
    finished in 12 s).

    `mesh`: a 1-D `jax.sharding.Mesh` shards the BFS *frontier* of this
    single search across devices (_make_block_fn_sharded) — the
    within-search parallel axis, complementing the across-keys axis of
    ops/wgl_batched.py.  The witness tier stays single-device (its
    frontier is a handful of lanes)."""
    import jax
    import jax.numpy as jnp

    t0 = time.monotonic()
    if mesh is not None:
        # Validate up front, before any search work: the frontier and
        # candidate budget shard evenly only over power-of-two mesh
        # sizes (beam sizes are power-of-two buckets).  NOTE the
        # sharded path also assumes a single-host mesh — the
        # window-boundary re-gather pulls the frontier to the host.
        n_dev = int(mesh.devices.size)
        b0 = _bucket(beam)
        if n_dev < 1 or b0 % n_dev or (cand_factor * b0) % n_dev:
            raise ValueError(
                f"mesh size {n_dev} must evenly divide the beam "
                f"bucket {b0} and its candidate budget"
            )

    N = packed.n
    if N == 0 or packed.n_ok == 0:
        return WGLResult(valid=True, configs_explored=1, elapsed_s=time.monotonic() - t0)

    if witness:
        from .wgl_witness import (
            NARROW_INFO_WINDOW,
            WIDE_INFO_WINDOW,
            check_wgl_witness,
            plan_drops,
        )

        # Window-width ladder: the narrow default first (fastest,
        # covers almost every valid history), then a wide retry whose
        # extra helper columns recover most of the completeness the
        # narrow info_window trades away.  Each rung gets the budget
        # REMAINING after earlier rungs and only pays a compile if its
        # W lands in a new bucket.  The wide rung runs only when the
        # narrow plan actually dropped info columns (checked lazily,
        # off the happy path) — otherwise both plans are identical and
        # the retry would deterministically fail again.
        def remaining() -> Optional[float]:
            if time_limit_s is None:
                return None
            return time_limit_s - (time.monotonic() - t0)

        def timed_out() -> bool:
            r = remaining()
            return r is not None and r <= 0

        with profile.capture(
            "witness", ops=int(N), ok=int(packed.n_ok),
        ) as _pw:
            _pw.knob(info_window=NARROW_INFO_WINDOW,
                     width_hint=width_hint)
            # Each rung and the re-plan between them has a span inside
            # `wgl.witness`, which the benchmark's `witness_s` reads
            # whole.
            with telemetry.span("wgl.witness"):
                with telemetry.span("wgl.witness.narrow"):
                    wres = check_wgl_witness(
                        packed, pm, info_window=NARROW_INFO_WINDOW,
                        time_limit_s=remaining(), width_hint=width_hint,
                        checkpoint_dir=checkpoint_dir,
                    )
                if wres is None:
                    telemetry.count("wgl.witness.narrow-miss")
                    if not timed_out():
                        with telemetry.span("wgl.witness.plan-drops"):
                            drops = plan_drops(
                                packed, info_window=NARROW_INFO_WINDOW)
                        if drops:
                            _pw.knob(info_window=WIDE_INFO_WINDOW)
                            telemetry.count("wgl.witness.widened")
                            with telemetry.span("wgl.witness.wide"):
                                wres = check_wgl_witness(
                                    packed, pm,
                                    info_window=WIDE_INFO_WINDOW,
                                    time_limit_s=remaining(),
                                    width_hint=width_hint,
                                    checkpoint_dir=checkpoint_dir,
                                )
            _pw.outcome = "hit" if wres is not None else "miss"
        if wres is not None:
            telemetry.count("wgl.witness.hit")
            return wres
        telemetry.count("wgl.witness.miss")
        if timed_out():
            return WGLResult(
                valid="unknown",
                configs_explored=0,
                reason="time-limit",
                elapsed_s=time.monotonic() - t0,
            )

    def _bfs() -> WGLResult:
        SW = pm.state_width
        n0 = 0
        B = _bucket(beam, lo=256)
        packed_on = packed_enabled(packed_lanes)
        prev_active: Optional[np.ndarray] = None
        member = None  # device (B, W) bool, or (B, ceil(W/32)) u32 packed
        states = None  # device (B, SW) i32
        alive = None   # device (B,) bool
        explored_total = 0
        soft_incomplete = False  # gave up on exactness somewhere
        device_retried = False   # one halved-beam retry on resource errors

        while n0 < N:
            win = _window_tables(packed, n0, block, max_window)
            if win is None:
                return WGLResult(
                    valid="unknown",
                    configs_explored=explored_total,
                    reason="window-overflow",
                    elapsed_s=time.monotonic() - t0,
                )
            active, W, tables = win
            h1v, h2v, sh1v, sh2v = _hash_vectors(W, SW)
            Wp = packing.n_words(W)

            # Re-gather frontier bits from the previous window layout.
            if prev_active is None:
                if packed_on:
                    base_member = np.zeros((B, Wp), dtype=np.uint32)
                else:
                    base_member = np.zeros((B, W), dtype=bool)
                base_states = np.tile(
                    np.asarray(pm.init_state, dtype=np.int32), (B, 1)
                )
                base_alive = np.zeros(B, dtype=bool)
                base_alive[0] = True
                member = jnp.asarray(base_member)
                states = jnp.asarray(base_states)
                alive = jnp.asarray(base_alive)
            else:
                # Host-side re-gather: device gathers here recompile per
                # distinct (old, new) window shape pair and dominate runtime.
                perm, present = window_regather(prev_active, active)
                member_np = np.asarray(member)
                if packed_on:
                    member_np = packing.np_unpack_bits(
                        member_np, member_np.shape[1] * packing.LANES
                    )
                Bcur = member_np.shape[0]
                new_member = np.zeros((Bcur, W), dtype=bool)
                new_member[:, : len(active)] = np.where(
                    present[None, :], member_np[:, perm], False
                )
                if packed_on:
                    new_member = packing.np_pack_bits(new_member, Wp)
                member = jnp.asarray(new_member)

            iters = min(block, N - n0)
            # Snapshot for beam-overflow retry.
            snap = (member, states, alive)

            while True:
                Cmax = cand_factor * B
                # The step fn itself keys the cache (strong ref): an
                # id() key can collide after GC address reuse and serve
                # the wrong model's transition kernel.
                key = (B, W, SW, Cmax, pm.jax_step, mesh, packed_on)
                fn = _block_fn_cache.get(key)
                fresh_fn = fn is None
                if fn is None:
                    if mesh is not None:
                        fn = _make_block_fn_sharded(
                            B, W, SW, Cmax, pm.jax_step, mesh,
                            packed=packed_on,
                        )
                    else:
                        fn = _make_block_fn(
                            B, W, SW, Cmax, pm.jax_step, packed=packed_on
                        )
                    _block_fn_cache[key] = fn
                if packed_on:
                    # Packed table slots: ok-mask as uint32 words, hash
                    # vectors as odd uint32 multipliers.
                    htabs = [
                        jnp.asarray(packing.np_pack_bits(tables["ok_w"], Wp)),
                        jnp.asarray(tables["fmin1"]),
                        jnp.asarray(tables["f_has_ok"]),
                        jnp.asarray(packing.hash_consts(Wp, 0)),
                        jnp.asarray(packing.hash_consts(Wp, 1)),
                        jnp.asarray(packing.hash_consts(SW, 2)),
                        jnp.asarray(packing.hash_consts(SW, 3)),
                    ]
                else:
                    htabs = [
                        jnp.asarray(tables["ok_w"]),
                        jnp.asarray(tables["fmin1"]),
                        jnp.asarray(tables["f_has_ok"]),
                        jnp.asarray(h1v),
                        jnp.asarray(h2v),
                        jnp.asarray(sh1v),
                        jnp.asarray(sh2v),
                    ]
                targs = [
                    jnp.asarray(tables["ret_w"]),
                    jnp.asarray(tables["inv_w"]),
                    jnp.asarray(tables["f_w"]),
                    jnp.asarray(tables["a0_w"]),
                    jnp.asarray(tables["a1_w"]),
                ] + htabs
                if telemetry.enabled():
                    # Fresh cache entries pay jit trace+compile inside the
                    # first call — "wgl.bfs.compile" vs "wgl.bfs.block" is
                    # the compile/execute split the phase profile reports.
                    telemetry.count(
                        "wgl.h2d-bytes",
                        int(sum(a.nbytes for a in tables.values()
                                if hasattr(a, "nbytes"))),
                    )
                    telemetry.gauge("wgl.bfs.beam", B)
                    telemetry.gauge("wgl.bfs.window", W)
                    if packed_on:
                        telemetry.count("wgl.packed.blocks")
                        telemetry.gauge("wgl.packed.words", Wp)
                    sp = telemetry.span(
                        "wgl.bfs.compile" if fresh_fn else "wgl.bfs.block"
                    )
                else:
                    sp = telemetry.span("")  # shared no-op
                try:
                    degrade.maybe_fault("device")
                    # The bool() syncs stay inside the try: jitted dispatch
                    # is async, so execution failures raise at consumption.
                    with sp:
                        out = fn(member, states, alive, jnp.int32(iters), *targs)
                        member, states, alive, accepted, incomplete, explored, it_done = out
                        accepted_b = bool(accepted)
                        incomplete_b = bool(incomplete)
                except Exception as e:  # noqa: BLE001
                    if not degrade.is_resource_error(e):
                        raise
                    # Degradation ladder: the device (not the search) gave
                    # out.  Evict the compiled block fn, retry ONCE with a
                    # halved beam from the block snapshot, then settle for
                    # "unknown" — the dispatcher's CPU settle takes over.
                    _block_fn_cache.pop(key, None)
                    if packed_on:
                        # First rung: shed the packed lanes and retry the
                        # block wide at the SAME beam — packing is an
                        # optimisation, not a budget, so it goes before
                        # any beam width is surrendered.
                        packed_on = False
                        degrade.record("device", "packed-fallback", e)
                        telemetry.count("wgl.packed.fallbacks")
                        m0, s0, a0_ = snap
                        m0np = np.asarray(m0)
                        member = jnp.asarray(packing.np_unpack_bits(
                            m0np, m0np.shape[1] * packing.LANES
                        )[:, :W])
                        states, alive = s0, a0_
                        snap = (member, states, alive)
                        continue
                    if device_retried or B <= 64:
                        degrade.record("device", "fall-through", e)
                        return WGLResult(
                            valid="unknown",
                            configs_explored=explored_total,
                            reason="device-resource-error",
                            elapsed_s=time.monotonic() - t0,
                        )
                    device_retried = True
                    degrade.record("device", "retry-halved", e)
                    B //= 2
                    m0, s0, a0_ = snap
                    # Frontier rows are packed alive-first; truncating live
                    # rows beyond the new beam forfeits exactness, which
                    # soft_incomplete degrades to "unknown" (never a false
                    # conviction).
                    if bool(a0_[B:].any()):
                        soft_incomplete = True
                    member = m0[:B]
                    states = s0[:B]
                    alive = a0_[:B]
                    snap = (member, states, alive)
                    continue
                if telemetry.enabled():
                    telemetry.count("wgl.bfs.rounds", int(it_done))

                if accepted_b:
                    explored_total += int(explored)
                    return WGLResult(
                        valid=True,
                        configs_explored=explored_total,
                        elapsed_s=time.monotonic() - t0,
                    )
                if time_limit_s is not None and time.monotonic() - t0 > time_limit_s:
                    # The limit must bind inside the retry ladder too —
                    # round-1 bug: a 45 s limit was ignored for 280 s+ while
                    # the ladder doubled and recompiled.
                    return WGLResult(
                        valid="unknown",
                        configs_explored=explored_total + int(explored),
                        reason="time-limit",
                        elapsed_s=time.monotonic() - t0,
                    )
                if incomplete_b and B < max_beam:
                    # Retry this block with a wider beam, exactly.
                    B *= 2
                    m0, s0, a0_ = snap
                    pad = B - m0.shape[0]
                    member = jnp.pad(m0, ((0, pad), (0, 0)))
                    states = jnp.pad(s0, ((0, pad), (0, 0)))
                    alive = jnp.pad(a0_, (0, pad))
                    snap = (member, states, alive)
                    continue
                if incomplete_b:
                    soft_incomplete = True
                explored_total += int(explored)
                break

            if not bool(alive.any()):
                if soft_incomplete:
                    return WGLResult(
                        valid="unknown",
                        configs_explored=explored_total,
                        reason="beam-overflow",
                        elapsed_s=time.monotonic() - t0,
                    )
                return WGLResult(
                    valid=False,
                    configs_explored=explored_total,
                    elapsed_s=time.monotonic() - t0,
                )
            if time_limit_s is not None and time.monotonic() - t0 > time_limit_s:
                return WGLResult(
                    valid="unknown",
                    configs_explored=explored_total,
                    reason="time-limit",
                    elapsed_s=time.monotonic() - t0,
                )
            n0 += int(it_done)
            prev_active = active

        # Ran every level with live configs and never accepted: with an exact
        # search this is unreachable (a full linearization covers all oks);
        # degrade safely.
        return WGLResult(
            valid="unknown" if soft_incomplete else False,
            configs_explored=explored_total,
            reason="exhausted",
            elapsed_s=time.monotonic() - t0,
        )

    # The BFS pass record: shape features + plan knobs + the
    # compile/execute split folded in from the wgl.bfs.compile /
    # wgl.bfs.block spans via the span-exit hook (telemetry/profile.py).
    with profile.capture(
        "bfs", ops=int(N), ok=int(packed.n_ok),
    ) as _pb:
        _pb.knob(
            beam=int(_bucket(beam, lo=256)), block=int(block),
            max_beam=int(max_beam), max_window=int(max_window),
            mesh=mesh is not None,
            packed=packed_enabled(packed_lanes),
        )
        res = _bfs()
        _pb.outcome = (f"unknown:{res.reason}"
                       if res.valid == "unknown" else res.valid)
        _pb.feature(explored=int(res.configs_explored))
    return res
