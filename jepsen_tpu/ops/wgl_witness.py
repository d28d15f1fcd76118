"""Device witness search for linearizability — the valid-verdict fast path.

Round-1 finding: the level-synchronous BFS in ops/wgl.py carries every
reachable subset of absorbed indeterminate (:info) ops as a distinct
configuration, so frontier width grows ~2^k with accumulated info ops
(the deliberately adversarial BASELINE.json 100k-op high-:info config).
This module is the algorithmic answer: an *event-walk* formulation of
Wing–Gong (the just-in-time linearization strategy of Lowe's "Testing
for Linearizability" — the same algorithm family knossos's
`knossos.wgl/analysis` implements, consumed by the reference at
jepsen/src/jepsen/checker.clj:214-233):

* Walk :ok operations in completion order.  By induction every :ok op
  returning before the current barrier is linearized in every surviving
  config, so the WGL candidate rule — `a` may be linearized iff
  inv(a) < min ret over non-members — collapses to "invoked before the
  current barrier's return".
* At the barrier for op `a`, each config must contain `a`: configs pass
  (a already linearized as an earlier helper), linearize `a` directly
  (one model step per beam lane), or linearize a *chain* of helper ops
  ending in `a`.  Helpers are ops still open at the barrier:
  indeterminate ops (ret = ∞, never forced) and :ok ops returning later.
* Chains are found just-in-time, vectorized: a targeted round evaluates
  every (lane, helper) pair `h·a` in one batched model step; an
  escalation round expands by any *productive* single helper
  (state-changing — an unproductive helper child is dominated by its
  parent), deduplicates children by resulting model state, and retries.
  Info ops are therefore only linearized at the barrier that needs
  their effect — the frontier never enumerates subsets of irrelevant
  info ops.

Execution is shaped by two measured costs (round-2 profiling):

* XLA recompilation: anything shape-polymorphic per block (window
  width, re-gather permutations) recompiles hundreds of times.  The
  window width W is therefore fixed for the whole run (the max over
  blocks, bucketed), so exactly one chunk kernel is compiled, and the
  between-block member re-layout is a static-shape device gather driven
  by per-block permutation tensors.
* Dispatch latency (a fixed cost per device call): barriers are
  grouped into blocks of `bars_per_block`, and `blocks_per_call` blocks
  ship per device call — a 100k-op history runs in ~3 calls.  Inside a
  call, an outer `lax.scan` over blocks re-lays the window and scans
  the block's barriers once: the body does the pass/direct step inline
  (membership of ops whose barrier passed is *implied by barrier rank*,
  so direct linearizations write no member bits) and enters the heavy
  chain-search round behind a `lax.cond` only at barriers where the
  frontier would die.  (An earlier fast-scan/heavy/re-scan split spent
  ~85% of device time re-walking blocks after each heavy round.)

Soundness: every transition is a legal WGL linearization step, so any
config alive after the final barrier is a witness — `valid=True` is
exact.  The search is *not* exhaustive (beam + chain-depth bounded, and
direct success suppresses early-linearization branches), so a dead
frontier proves nothing: callers fall back to the exact frontier BFS
(ops/wgl.py) / CPU DFS (checker/wgl_cpu.py) for invalid/unknown.
"""

from __future__ import annotations

import hashlib
import os
import time
from typing import Any, Optional

import numpy as np

from .. import telemetry
from ..telemetry import profile, roofline
from ..checker.wgl_cpu import WGLResult
from ..history.packed import ST_OK, PackedOps
from ..models.base import PackedModel
from . import degrade, packing
from .wgl import _bucket, packed_enabled, window_regather

INF = np.int32(2**31 - 1)
NO_BAR = np.iinfo(np.int32).max

#: Default per-block bound on indeterminate-op window columns.  Narrow
#: on purpose: W buckets to 2048 on the bench config (1.8 s vs 3.2 s at
#: 4096 — round-2 measurement).  check_wgl_device escalates to
#: WIDE_INFO_WINDOW when a narrow attempt that actually dropped columns
#: finds no witness.  bench.py's warm-up precompiles via plan_width,
#: which shares this default — keep them coupled through this constant.
NARROW_INFO_WINDOW = 512
WIDE_INFO_WINDOW = 4096

_chunk_fn_cache: dict[tuple, Any] = {}

#: transfer="device" entries, keyed (chunk-fn key, span-slice bucket):
#: separate from _chunk_fn_cache so the span bucket never fragments
#: the eager (fn, fn_idx) build.
_chunk_dev_cache: dict[tuple, Any] = {}

#: Scalar memory the Pallas sweep may fill (v5e: 1 MiB).  The (6, K)
#: barrier table pads to 8 sublanes and the (W,) member words sit
#: beside it; a v5e compile at K=32768 was refused with "Ran out of
#: memory in memory space smem. Used 1.12M of 1.00M".
PALLAS_SMEM_BYTES = 1 << 20


def pallas_smem_bytes(bars_per_block: int, window: int) -> int:
    """SMEM the Pallas sweep's tables take at one block shape."""
    return 4 * (8 * bars_per_block + window)


#: Minimum elapsed seconds before a checkpoint is worth writing: short
#: searches finish in milliseconds and would pay a device->host carry
#: transfer + npz write per chunk for a file that is deleted moments
#: later.  A blown budget saves regardless — that is precisely the
#: run whose progress a resume recovers.
CKPT_MIN_ELAPSED_S = 5.0


def _ckpt_key(packed: PackedOps, pm: PackedModel, B: int, W: int,
              SW: int, K: int, NB: int,
              info_window: Optional[int]) -> str:
    """Digest binding a checkpoint to one (history, model, search
    shape) triple.  The FULL packed arrays are hashed — a collision
    here would resume the wrong search and corrupt a verdict, so no
    sampling shortcuts (~0.25 s at 10M rows, microseconds at bench
    sizes, amortized over minutes of resumable work).  The model's
    identity and initial state are in the key because the carry's
    beam states only mean anything under the transition function
    that computed them."""
    h = hashlib.sha256()
    h.update(np.int64(
        [packed.n, B, W, SW, K, NB, -1 if info_window is None
         else info_window]
    ).tobytes())
    h.update(getattr(pm, "name", type(pm).__name__).encode())
    h.update(np.ascontiguousarray(
        np.asarray(pm.init_state, dtype=np.int64)
    ).tobytes())
    for name in ("inv", "ret", "process", "status", "f", "a0", "a1"):
        h.update(np.ascontiguousarray(getattr(packed, name)).tobytes())
    return h.hexdigest()


def _ckpt_load(path: str, key: str):
    """-> (next_chunk_c0, member, states, alive) or None."""
    import zipfile

    try:
        with np.load(path, allow_pickle=False) as z:
            if str(z["key"]) != key:
                return None
            return (int(z["c0"]), z["member"], z["states"], z["alive"])
    except (FileNotFoundError, OSError, KeyError, ValueError,
            zipfile.BadZipFile):
        # Missing, foreign, or torn (np.savez never fsyncs, so a hard
        # kill mid-save can install a partial zip): restart from
        # block zero rather than crash the analysis.
        return None


def _ckpt_save(path: str, key: str, c0: int, member: np.ndarray,
               states: np.ndarray, alive: np.ndarray) -> None:
    # NB: np.savez appends ".npz" to names that lack it — the tmp
    # name must already end in .npz or os.replace misses the real
    # file and the except clause eats the evidence.
    tmp = path + ".tmp.npz"
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        np.savez(tmp, key=key, c0=np.int64(c0), member=member,
                 states=states, alive=alive)
        os.replace(tmp, path)
    except OSError:
        # Checkpointing is best-effort: a full disk must not cost
        # the verdict.
        pass


def _ckpt_remove(path: Optional[str]) -> None:
    if path is None:
        return
    try:
        os.remove(path)
    except OSError:
        pass


def _state_hash_vec(sw: int, seed: int = 0xA11CE) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.uniform(1.0, 2.0, size=(sw,)).astype(np.float32)


#: The hash of a chain-round candidate that is no child.
NO_CHILD = np.float32(3.0e38)


def _pick_children(h, child_states, B: int):
    """At most B candidates of distinct state, by hash.

    `h` (M,) f32 is each candidate's state hash, NO_CHILD where it is
    no child; `child_states` (M, SW) its state.  Each of B passes keeps
    the candidate of least hash (lowest index on a tie) and masks every
    candidate of that hash and that state, so the kept candidates come
    in ascending (hash, index) order.  Equal states always hash equal;
    distinct states whose hashes collide are each kept.  Returns `pos`
    (B,), the kept indices, padded with the first; and `found` (B,)
    bool, true for the passes that kept one (a prefix).
    """
    import jax.numpy as jnp

    picks, found = [], []
    for _ in range(B):
        i = jnp.argmin(h)
        picks.append(i)
        found.append(h[i] < NO_CHILD)
        h = jnp.where(
            (h == h[i]) & (child_states == child_states[i]).all(axis=1),
            NO_CHILD, h,
        )
    found = jnp.stack(found)
    return jnp.where(found, jnp.stack(picks), picks[0]), found


def _plan_blocks(packed: PackedOps, bars_per_block: int,
                 info_window: Optional[int] = None,
                 rank_override: Optional[np.ndarray] = None):
    """Host-side plan: barrier order, per-block active windows.

    `info_window` keeps only the most recently invoked N indeterminate
    ops in each block's window.  Dropping an info column is SOUND for
    the witness tier regardless of its membership state — an
    unlinearized one merely stops being a helper candidate
    (completeness loss only), and a linearized one keeps its state
    contribution while becoming un-relinearizable.  Without the bound,
    info ops accumulate for the whole run (ret = ∞) and the window —
    hence heavy-round cost — grows linearly with history length: the
    1M-op bench config reaches W = 65536 unbounded.

    The per-block window is maintained INCREMENTALLY: rows are
    invocation-ordered, so each block's entrants are the contiguous
    index range invoked since the previous block (one searchsorted),
    and its leavers are exactly the barriers that passed in the
    previous block plus the oldest info rows beyond the bound — both
    O(window) merges.  A fresh full-history mask per block (the
    round-1..3 implementation) made planning O(n_blocks * n): at 10M
    ops it dominated end-to-end time (measured 43.7k ops/s vs 190k at
    1M, i.e. the checker itself was linear but the planner wasn't).

    Returns (bars, bar_rank, inv32, ret32, blocks, any_dropped);
    `any_dropped` reports whether any block actually lost info columns
    to the bound — when False, a wider retry would plan identically.

    Raises OverflowError when any real event index is >= int32 INF:
    the int32 casts below would otherwise WRAP (negative inv) or clamp
    a real return to the info sentinel — either silently corrupts the
    barrier order.  Reachable via the stream checker's concatenated
    timeline (ops/wgl_stream.py accumulates E+2 per key); callers
    treat it as "witness tier unusable, escalate"."""
    status = packed.status
    if packed.n:
        t_max = int(packed.inv.max())
        okm = status == ST_OK
        if okm.any():
            t_max = max(t_max, int(packed.ret[okm].max()))
        if t_max >= int(INF):
            raise OverflowError(
                f"event timeline exceeds int32: max index {t_max} >= "
                f"{int(INF)}; witness tier cannot represent this history"
            )
    inv32 = packed.inv.astype(np.int32)
    ret32 = np.minimum(packed.ret, np.int64(INF)).astype(np.int32)
    ok_rows = np.nonzero(status == ST_OK)[0]
    bars = ok_rows[np.argsort(ret32[ok_rows], kind="stable")]
    bar_rank = np.full(packed.n, NO_BAR, dtype=np.int64)
    bar_rank[bars] = np.arange(len(bars))
    if rank_override is not None:
        # Stream semantics (ops/wgl_stream.py): a non-barrier row may
        # carry a synthetic rank — once that rank passes, the row is
        # treated exactly like a retired barrier (implied membership,
        # excluded from helper candidacy, dropped from later windows).
        # Barrier rows keep their real ranks: overriding one would
        # corrupt the sweep order.
        ov = (rank_override >= 0) & (status != ST_OK)
        bar_rank[ov] = rank_override[ov]
    is_info = status != ST_OK
    blocks = []
    any_dropped = False
    # active: sorted row indices currently in the window; hi: rows
    # [0, hi) have entered (inv32 is strictly increasing row-wise).
    active = np.empty(0, dtype=np.int64)
    hi = 0
    for k0 in range(0, len(bars), bars_per_block):
        block_bars = bars[k0 : k0 + bars_per_block]
        end_ret = int(ret32[block_bars[-1]])
        # Leavers: rows whose rank passed at block start — real
        # barriers from the previous block, plus override rows whose
        # synthetic rank passed (equivalent to the previous isin()
        # against the passed-barrier list: any active barrier with
        # rank < k0 was by construction in that list).
        if k0:
            active = active[bar_rank[active] >= k0]
        # Entrants: invoked before this block's last barrier.  New
        # rows have larger indices than everything already active, so
        # concatenation preserves sortedness.
        # np.int32 key: a python-int key makes numpy CAST THE WHOLE
        # 10M-row array per call (measured 50 ms vs 6 µs — it was 76%
        # of end-to-end time at 8M ops).
        hi_new = int(np.searchsorted(inv32, np.int32(end_ret),
                                     side="left"))
        if hi_new > hi:
            entering = np.arange(hi, hi_new, dtype=np.int64)
            # Rows whose barrier already passed never join.
            entering = entering[bar_rank[entering] >= k0]
            active = np.concatenate([active, entering])
            hi = hi_new
        if info_window is not None:
            info_mask = is_info[active]
            n_info = int(info_mask.sum())
            if n_info > info_window:
                # Keep the newest N info rows; the drop is permanent
                # ("newest N" is monotone as rows only get newer),
                # matching the per-block criterion of the full-mask
                # implementation.
                drop_pos = np.nonzero(info_mask)[0][: n_info - info_window]
                active = np.delete(active, drop_pos)
                any_dropped = True
        blocks.append((k0, block_bars, active))
    return bars, bar_rank, inv32, ret32, blocks, any_dropped


def plan_width(packed: PackedOps, bars_per_block: Optional[int] = None,
               info_window: Optional[int] = NARROW_INFO_WINDOW) -> int:
    """The window width a witness run over `packed` will use — lets a
    warm-up run pre-compile the same kernel via `width_hint`."""
    if packed.n == 0 or packed.n_ok == 0:
        return 0
    if bars_per_block is None:
        from ..plan.costmodel import choose_witness_block_knobs

        bars_per_block = choose_witness_block_knobs(
            packed.n, int(packed.n_ok))[0]["bars_per_block"]
    try:
        _, _, _, _, blocks, _ = _plan_blocks(packed, bars_per_block,
                                             info_window)
    except OverflowError:
        return 0  # witness tier can't run this history; nothing to warm
    return _bucket(max(max(len(a) for _, _, a in blocks), 1))


def plan_drops(packed: PackedOps, bars_per_block: Optional[int] = None,
               info_window: Optional[int] = NARROW_INFO_WINDOW) -> bool:
    """Whether a witness plan at this info_window would drop any info
    columns — when False, a wider window plans identically and an
    escalation retry is pointless."""
    if packed.n == 0 or packed.n_ok == 0 or info_window is None:
        return False
    if packed.n - packed.n_ok <= info_window:
        return False  # cheap bound: fewer info ops than the window
    if bars_per_block is None:
        from ..plan.costmodel import choose_witness_block_knobs

        bars_per_block = choose_witness_block_knobs(
            packed.n, int(packed.n_ok))[0]["bars_per_block"]
    try:
        return _plan_blocks(packed, bars_per_block, info_window)[5]
    except OverflowError:
        return False  # no witness run happens at all, so no drops


def _make_pallas_sweep(B: int, W: int, SW: int, K: int, jax_step_rows,
                       interpret: bool, unroll: int = 8):
    """The easy-path barrier sweep as a Pallas TPU kernel.

    The XLA `lax.scan` version pays ~30 µs of small-op critical path
    per barrier (round-2 measurement: 1.36 s for a 47k-barrier 0-info
    history).  Here the whole sweep runs inside one kernel whose state
    (member bits, beam states, alive mask) stays on-chip, with a
    `while_loop` that exits at the first barrier the easy path cannot
    survive — the heavy chain search stays in XLA and resumes the
    sweep afterwards.

    Mosaic constraints shape the layout: dynamic per-barrier scalar
    reads must come from SMEM (VMEM vector loads need statically
    aligned indices), so the barrier table lives in SMEM and the
    member matrix is BIT-PACKED to one int32 word per window row
    ((W,) in SMEM; lane b of the beam is bit b — arithmetic
    right-shift + &1 extracts bits for any B <= 32).  All vector
    state is LANE-MAJOR (beam lanes on the 128-lane axis: states
    (SW, B), masks (1, B)) and 32-bit, because sub-32-bit relayouts
    and lane<->sublane reshapes don't lower.

    Outputs: states', alive', death (1,1) SMEM i32 — death == K means
    the block completed; any smaller value is the barrier index whose
    pass/direct step would have killed the frontier (state/alive
    returned are from just BEFORE that barrier).  Identical
    transition semantics to the `easy` branch of the scan path."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    UNROLL = max(1, unroll)

    def kernel(start_ref, bars_ref, mbits_ref, states_ref, alive_ref,
               states_out, alive_out, death_ref):
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, B), 1)
        start = start_ref[0, 0]
        states0 = states_ref[:]          # (SW, B) i32
        alive0 = alive_ref[:]            # (1, B) i32 0/1

        # All VECTOR masks are int32 0/1 — Mosaic fails to legalize
        # selects that produce bool vectors; scalar bools (loop
        # control) are fine.
        def cond(c):
            k, _, _, died = c
            return jnp.logical_and(k < K, jnp.logical_not(died))

        # One barrier's transition, guarded so a finished (dead or
        # past-the-end) carry passes through unchanged.  The guard is
        # what lets the while body UNROLL U barriers per iteration:
        # the live-chip measurement behind it is ~5.2 us/barrier at
        # U=1 — Mosaic's per-iteration loop machinery (cond eval +
        # carry) costs more than the barrier math itself, the same
        # finding as the round-2 XLA-scan measurement, one level down.
        def step1(k, states, alive, died):
            kk = jnp.minimum(k, K - 1)
            a = bars_ref[0, kk]
            valid = jnp.logical_and(k < K, jnp.logical_not(died))
            real = jnp.logical_and(valid, bars_ref[2, kk] != 0)
            bf = bars_ref[3, kk]
            ba0 = bars_ref[4, kk]
            ba1 = bars_ref[5, kk]
            bits = mbits_ref[a]
            has = (bits >> lane) & 1                   # (1, B) i32
            ns, legal_b = jax_step_rows(states, bf, ba0, ba1)
            legal = legal_b.reshape(1, B).astype(jnp.int32)
            surv_pass = alive & has
            surv_dir = alive & (1 - has) & legal
            new_alive = surv_pass | surv_dir
            died_k = real & (new_alive.max() == 0)     # scalar bool
            commit_i = jnp.where(real & ~died_k, 1, 0)  # scalar i32
            take = commit_i * surv_dir                 # (1, B) i32
            st = jnp.where(take != 0, ns, states)
            al = commit_i * new_alive + (1 - commit_i) * alive
            k2 = jnp.where(valid & ~died_k, k + 1, k)
            return k2, st, al, died | died_k

        def body(c):
            k, states, alive, died = c
            for _ in range(UNROLL):
                k, states, alive, died = step1(k, states, alive, died)
            return (k, states, alive, died)

        k, states, alive, died = jax.lax.while_loop(
            cond, body, (start, states0, alive0, jnp.bool_(False))
        )
        states_out[:] = states
        alive_out[:] = alive
        death_ref[0, 0] = jnp.where(died, k, K)

    call = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((SW, B), jnp.int32),
            jax.ShapeDtypeStruct((1, B), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ),
        in_specs=[
            pl.BlockSpec((1, 1), memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), memory_space=pltpu.SMEM),
        ),
        interpret=interpret,
        name="wgl_sweep",
    )

    def sweep(start_k, bars, member, states, alive):
        start = jnp.asarray(start_k, jnp.int32).reshape(1, 1)
        # Pack each member row to one int32 word (lane b -> bit b).
        mbits = (
            member.astype(jnp.int32)
            << jnp.arange(B, dtype=jnp.int32)[None, :]
        ).sum(axis=1).astype(jnp.int32)
        s2, al2, dk = call(
            start, bars, mbits, states.T,
            alive[None, :].astype(jnp.int32),
        )
        return s2.T, al2[0] != 0, dk[0, 0]

    return sweep


def _make_chunk_fn(B: int, W: int, SW: int, K: int, D: int, NB: int,
                   jax_step, pallas_mode: str = "off",
                   jax_step_rows=None, packed: bool = False):
    """One call runs NB blocks of up to K barriers each.

    Args: member (W, B) bool — window-major so the per-barrier
    membership lookup member[a] is a fast major-axis row slice (a
    (B, W) layout makes it a minor-axis dynamic gather) —, states
    (B, SW) i32, alive (B,) bool, failed () bool, and per-block
    tensors — bars (NB, 6, K) i32 (rows: window col, ret, real, and
    the barrier op's f/a0/a1 pre-gathered on host so the hot scan does
    no table lookups), tab (NB, 5, W) i32 (rows: inv, f, a0, a1,
    bar_rank — the heavy round's helper tables), perm (NB, W) i32 +
    present (NB, W) bool (member re-layout from the previous block's
    window), k0s (NB,) i32 (global rank of each block's first
    barrier).  Padding blocks pass identity perm/present and zero
    `real` flags and are no-ops.

    The heavy chain search runs INSIDE the barrier scan behind a
    lax.cond — round-2 profiling showed the earlier design (fast scan
    to the death point, heavy round, masked re-scan) spent ~85% of
    device time re-scanning: each of the ~458 heavy rounds on the
    100k-op bench re-walked up to K barriers.  Inline, every barrier
    is visited exactly once.

    Flat (helper, lane) pair indexing is helper-major: i = h*B + lane.
    A chain round runs the pair step over the whole (W, B) tile and
    keeps at most B children with B masked-min passes over the
    candidates' state hashes: no sort, no scatter, no cumsum.

    Every entry also returns `rounds`, the number of chain rounds the
    call ran (counter `wgl.witness.chain-rounds`).
    """
    import jax
    import jax.numpy as jnp

    col = jnp.arange(W)
    hv = jnp.asarray(_state_hash_vec(SW))

    # `packed`: the (W, B) member window rides the inter-block scan
    # carry — and the per-block re-gather, the engine's hottest
    # relayout — as ceil(B/32) uint32 beam lanes (ops/packing.py).
    # run_block itself still sees the bool window (unpack on entry,
    # pack on exit), so block semantics are bit-identical; only the
    # carried/gathered bytes shrink.
    Bp = packing.n_words(B)
    zero_m = jnp.uint32(0) if packed else False

    def _pack_m(m):
        return packing.pack_bits(m, Bp) if packed else m

    def _unpack_m(mw):
        return packing.unpack_bits(mw, B) if packed else mw

    pallas_sweep = (
        _make_pallas_sweep(
            B, W, SW, K, jax_step_rows,
            interpret=(pallas_mode == "interpret"),
        )
        if pallas_mode != "off"
        else None
    )

    def run_block(member, states, alive, bars, tab, k0):
        inv_w, f_w, a0_w, a1_w, bar_rank_w = (
            tab[0], tab[1], tab[2], tab[3], tab[4],
        )

        def pair_steps(states_rep, f_r, a0_r, a1_r):
            # helper-major: rows h*B+lane pair helper h with lane's state
            return jax.vmap(jax_step)(
                states_rep,
                jnp.repeat(f_r, B),
                jnp.repeat(a0_r, B),
                jnp.repeat(a1_r, B),
            )

        def select_children(member, child_states, good):
            """Dedup (helper, lane) children by model state, keep <= B.

            Selection happens over flat-pair scalars FIRST; member
            columns are materialized only for the <= B winners —
            building (W*B, W) child-member matrices up front costs
            ~B*W*W bytes."""
            h = jnp.where(good, child_states.astype(jnp.float32) @ hv,
                          NO_CHILD)
            pos, found = _pick_children(h, child_states, B)
            lane = pos % B
            new_member = member[:, lane] | (
                col[:, None] == (pos // B)[None, :]
            )
            return new_member, child_states[pos], found

        def heavy(member, states, alive, a, r, bf, ba0, ba1, k_rank):
            """Chain search at one barrier: direct -> targeted h·a ->
            expand-any, bounded by chain depth D."""
            # Membership of ops whose barrier already passed is implied.
            implied = bar_rank_w < k_rank

            def step_bar(s):
                return jax_step(s, bf, ba0, ba1)

            def helper_avail(member, alive):
                # (W, B): helper rows x lanes
                return (
                    alive[None, :]
                    & ~member
                    & ~implied[:, None]
                    & (inv_w[:, None] < r)
                    & (col[:, None] != a)
                )

            def try_direct(member, states, alive):
                ns, legal = jax.vmap(step_bar)(states)
                has = member[a]
                surv_pass = alive & has
                surv_dir = alive & ~has & legal
                new_alive = surv_pass | surv_dir
                new_states = jnp.where(surv_dir[:, None], ns, states)
                return member, new_states, new_alive

            def targeted_or_expand(member, states, alive):
                """One fused escalation over the (W, B) candidate
                tile: the helper pair-step is evaluated ONCE and feeds
                both the targeted test (helper+barrier legal -> done)
                and the expand-any fallback (any productive helper ->
                keep searching)."""
                flat = helper_avail(member, alive).reshape(-1)
                states_rep = jnp.tile(states, (W, 1))
                s1, legal1 = pair_steps(states_rep, f_w, a0_w, a1_w)
                s2, legal2 = jax.vmap(step_bar)(s1)
                good_t = flat & legal1 & legal2
                ok2 = good_t.any()
                productive = legal1 & (s1 != states_rep).any(axis=1)
                good_e = flat & productive
                child = jnp.where(ok2, s2, s1)
                good = jnp.where(ok2, good_t, good_e)
                cm, cs, ca = select_children(member, child, good)
                return cm, cs, ca, ok2

            def cond(c):
                _, _, alive, done, d, _ = c
                return (~done) & (d < D) & alive.any()

            def body(c):
                member, states, alive, _, d, rounds = c
                m1, s1, al1 = try_direct(member, states, alive)

                def on_direct(_):
                    return m1, s1, al1, True

                def no_direct(_):
                    return targeted_or_expand(member, states, alive)

                mN, sN, alN, done = jax.lax.cond(
                    al1.any(), on_direct, no_direct, None
                )
                return (mN, sN, alN, done, d + 1,
                        rounds + jnp.where(al1.any(), 0, 1))

            member, states, alive, done, _, rounds = jax.lax.while_loop(
                cond, body,
                (member, states, alive, False, 0, jnp.int32(0)),
            )
            return member, states, alive, done, rounds

        if pallas_sweep is not None:
            # ---- pallas hybrid: VMEM sweep to the next death point,
            # heavy in XLA, resume — all under one while_loop ----
            def cond_w(c):
                k, _, _, _, failed, _, _ = c
                return (k < K) & ~failed

            def body_w(c):
                k, member, states, alive, failed, died, rounds = c
                s2, al2, dk = pallas_sweep(k, bars, member, states, alive)

                def clean(_):
                    return (jnp.int32(K), member, s2, al2, failed, died,
                            rounds)

                def death(_):
                    colv = jax.lax.dynamic_slice(
                        bars, (jnp.int32(0), dk), (6, 1)
                    )[:, 0]
                    m, s, al, done, r = heavy(
                        member, s2, al2, colv[0], colv[1], colv[3],
                        colv[4], colv[5], k0 + dk,
                    )
                    d2 = jnp.where(~done & (died == NO_BAR),
                                   k0 + dk, died)
                    return (dk + 1, m, s, al, failed | ~done, d2,
                            rounds + r)

                return jax.lax.cond(dk >= K, clean, death, None)

            return jax.lax.while_loop(
                cond_w, body_w,
                (jnp.int32(0), member, states, alive, jnp.bool_(False),
                 jnp.int32(NO_BAR), jnp.int32(0)),
            )[1:]

        # ---- barrier scan: pass/direct inline, heavy behind a cond ----
        def body(carry, xs):
            member, states, alive, failed, died, rounds = carry
            a, r, real, bf, ba0, ba1, k = xs
            has = member[a]
            ns, legal = jax.vmap(
                lambda s: jax_step(s, bf, ba0, ba1)
            )(states)
            surv_pass = alive & has
            surv_dir = alive & ~has & legal
            new_alive = surv_pass | surv_dir
            active = (real != 0) & ~failed

            def easy(_):
                commit = active & new_alive.any()
                st = jnp.where((commit & surv_dir)[:, None], ns, states)
                al = jnp.where(commit, new_alive, alive)
                return member, st, al, failed, died, rounds

            def hard(_):
                m, s, al, done, n = heavy(
                    member, states, alive, a, r, bf, ba0, ba1, k0 + k
                )
                d2 = jnp.where(~done & (died == NO_BAR), k0 + k, died)
                return m, s, al, failed | ~done, d2, rounds + n

            out = jax.lax.cond(
                active & ~new_alive.any(), hard, easy, None
            )
            return out, None

        carry0 = (member, states, alive, jnp.bool_(False),
                  jnp.int32(NO_BAR), jnp.int32(0))
        out, _ = jax.lax.scan(
            body, carry0,
            (bars[0], bars[1], bars[2], bars[3], bars[4], bars[5],
             jnp.arange(K, dtype=jnp.int32)),
        )
        return out

    def guarded_block(member, states, alive, failed, died, rounds,
                      bars_b, tab_b, k0):
        """run_block unless an earlier block failed; member arrives and
        leaves in carry form (_pack_m)."""
        def run(_):
            m, s, al, f2, d2, n = run_block(
                _unpack_m(member), states, alive, bars_b, tab_b, k0
            )
            return _pack_m(m), s, al, f2, d2, n

        def skip(_):
            return (member, states, alive, jnp.bool_(False),
                    jnp.int32(NO_BAR), jnp.int32(0))

        m, s, al, f2, d2, n = jax.lax.cond(~failed, run, skip, None)
        died = jnp.where((d2 != NO_BAR) & (died == NO_BAR), d2, died)
        return m, s, al, failed | f2, died, rounds + n

    def carry0(member, states, alive, failed):
        return (_pack_m(member), states, alive, failed,
                jnp.int32(NO_BAR), jnp.int32(0))

    def chunk(member, states, alive, failed, bars, tab, perm, present,
              k0s):
        def body(carry, xs):
            member, *rest = carry
            bars_b, tab_b, perm_b, present_b, k0 = xs
            member = jnp.where(present_b[:, None], member[perm_b],
                               zero_m)
            return guarded_block(member, *rest, bars_b, tab_b, k0), None

        (member, *rest), _ = jax.lax.scan(
            body, carry0(member, states, alive, failed),
            (bars, tab, perm, present, k0s),
        )
        return (_unpack_m(member), *rest)

    jcol = jnp.arange(K, dtype=jnp.int32)
    wcol = jnp.arange(W, dtype=jnp.int32)

    def idx_block_step(member, states, alive, failed, died, rounds,
                       bar_b, act_b, nb, nw, perm_b, present_b,
                       k0, fA, a0A, a1A, retA, invA, rankA):
        """One block: regather member (packed lanes when enabled),
        build bar/tab tables on device from row indices, run.  Shared
        by the "indices" and "device" transfer modes; member arrives
        and leaves in carry form (_pack_m)."""
        member = jnp.where(present_b[:, None], member[perm_b],
                           zero_m)
        real = (jcol < nb).astype(jnp.int32)
        bars_b = jnp.stack([
            jnp.searchsorted(act_b, bar_b).astype(jnp.int32),
            retA[bar_b],
            real,
            fA[bar_b],
            a0A[bar_b],
            a1A[bar_b],
        ])
        valid_w = wcol < nw
        tab_b = jnp.stack([
            jnp.where(valid_w, invA[act_b], INF),
            jnp.where(valid_w, fA[act_b], 0),
            jnp.where(valid_w, a0A[act_b], 0),
            jnp.where(valid_w, a1A[act_b], 0),
            jnp.where(valid_w, rankA[act_b], NO_BAR),
        ])
        return guarded_block(member, states, alive, failed, died, rounds,
                             bars_b, tab_b, k0)

    def chunk_idx(member, states, alive, failed, bar_idx, act_idx,
                  nbars, nws, perm, present, k0s,
                  fA, a0A, a1A, retA, invA, rankA):
        """transfer="indices" entry: identical semantics to `chunk`,
        but the (NB, 6, K) bars and (NB, 5, W) tab tables are built
        ON DEVICE from per-block row-index arrays + the once-uploaded
        per-row tables (fA/a0A/a1A/retA/invA/rankA) — ~3x less
        host->device traffic per chunk.

        Padding contracts: bar_idx pads with 0 (masked by j >= nb:
        real=0 rows commit nothing), act_idx pads with packed.n
        (> every real row index, so searchsorted stays monotone;
        gathers clamp under jit and the nw mask discards the lanes).
        """
        def body(carry, xs):
            out = idx_block_step(
                *carry, *xs, fA, a0A, a1A, retA, invA, rankA,
            )
            return out, None

        (member, *rest), _ = jax.lax.scan(
            body, carry0(member, states, alive, failed),
            (bar_idx, act_idx, nbars, nws, perm, present, k0s),
        )
        return (_unpack_m(member), *rest)

    def make_chunk_dev(S: int):
        """Builds the transfer="device" entry for span-slice width S.
        Separate from the eager (fn, fn_idx) pair so the Pallas sweep
        build is keyed independently of S: two histories sharing every
        other shape must not re-pay the Mosaic lowering because their
        spans bucket differently."""
        return roofline.instrument(jax.jit(_chunk_dev_for(S)))

    def _chunk_dev_for(S: int):
        def chunk_dev(member, states, alive, failed, prev_act,
                      k0s, end_rets, los, nbars, cuts, n_total,
                      fA, a0A, a1A, retA, invA, rankA, icumA, barsA):
            return _chunk_dev_impl(
                S, member, states, alive, failed, prev_act,
                k0s, end_rets, los, nbars, cuts, n_total,
                fA, a0A, a1A, retA, invA, rankA, icumA, barsA,
            )
        return chunk_dev

    def _chunk_dev_impl(S, member, states, alive, failed, prev_act,
                        k0s, end_rets, los, nbars, cuts, n_total,
                        fA, a0A, a1A, retA, invA, rankA, icumA, barsA):
        """transfer="device" entry: the per-block index arrays the
        "indices" mode ships from the host (~0.7 MB/chunk) are
        PLANNED ON DEVICE from the once-uploaded row tables — the
        per-chunk H2D payload shrinks to five (NB,) scalars (~640 B).
        The host's _plan_blocks stays authoritative for the STATIC
        facts (W, S buckets, chunk boundaries, per-block scalars);
        the device reproduces its row sets exactly:

          mask(r) = r entered (inv < end_ret) & rank not passed
                    (>= k0) & info retention (info_cum > cut)

        over the (lo, lo+S) slice host planning proved covers the
        window.  `prev_act` (the previous block's window rows, padded
        with n_total) is carried on device across blocks AND chunk
        calls, so the member re-gather needs no host round trip.
        """
        scol = jnp.arange(S, dtype=jnp.int32)

        def body(carry, xs):
            *block_carry, prev_act = carry
            k0, er, lo, nb, cut = xs
            rows = lo + scol
            rows_c = jnp.minimum(rows, n_total - 1)
            inv_r = invA[rows_c]
            rank_r = rankA[rows_c]
            icum_r = icumA[rows_c]
            is_info = rank_r == NO_BAR
            mask = ((rows < n_total) & (inv_r < er) & (rank_r >= k0)
                    & (~is_info | (icum_r > cut)))
            nw = mask.sum()
            act_local = jnp.nonzero(mask, size=W, fill_value=S)[0]
            valid_w = wcol < nw
            act_b = jnp.where(
                valid_w, lo + jnp.minimum(act_local, S - 1), n_total
            ).astype(jnp.int32)
            pos = jnp.searchsorted(prev_act, act_b)
            pos_c = jnp.clip(pos, 0, W - 1)
            present_b = ((pos < W) & (prev_act[pos_c] == act_b)
                         & (act_b < n_total))
            perm_b = jnp.where(present_b, pos_c, 0)
            bar_b = jax.lax.dynamic_slice(barsA, (k0,), (K,))
            out = idx_block_step(
                *block_carry,
                bar_b, act_b, nb, nw, perm_b, present_b, k0,
                fA, a0A, a1A, retA, invA, rankA,
            )
            # Padding blocks (nb == 0 with er == 0) must not clobber
            # the carried window.
            new_prev = jnp.where(nb > 0, act_b, prev_act)
            return (*out, new_prev), None

        carry, _ = jax.lax.scan(
            body,
            (*carry0(member, states, alive, failed), prev_act),
            (k0s, end_rets, los, nbars, cuts),
        )
        return (_unpack_m(carry[0]),) + tuple(carry[1:])

    return (roofline.instrument(jax.jit(chunk)),
            roofline.instrument(jax.jit(chunk_idx)), make_chunk_dev)


def check_wgl_witness(
    packed: PackedOps,
    pm: PackedModel,
    *,
    beam: int = 8,  # 16 -> 8 measured 0.70 -> 0.51 s on the 100k bench;
    # chain diversity above 8 lanes almost never decides a register-
    # class history, and a died witness still escalates to the exact
    # tiers.
    bars_per_block: Optional[int] = None,  # None -> profile-chosen
    blocks_per_call: Optional[int] = None,  # bucket (plan/costmodel)
    depth: int = 5,
    info_window: Optional[int] = NARROW_INFO_WINDOW,
    max_window: int = 32768,
    width_hint: int = 0,
    time_limit_s: Optional[float] = None,
    pallas: str = "auto",
    checkpoint_dir: Optional[str] = None,
    transfer: str = "auto",
    rank_override: Optional[np.ndarray] = None,
    out_info: Optional[dict] = None,
    packed_lanes: Optional[bool] = None,
    _degraded: bool = False,
) -> Optional[WGLResult]:
    """Runs the witness search on the default JAX device.

    Returns an exact `WGLResult(valid=True)` when a witness linearization
    survives, or None when the search dies / overflows / times out —
    meaning "escalate to the exact search", never "invalid".

    `transfer`: "full" ships the pre-gathered (NB,6,K)+(NB,5,W) block
    tables per chunk call; "indices" uploads the per-row tables once
    and ships only small row-index arrays per chunk, rebuilding the
    tables on device — ~3x less H2D; "device" (round 5,
    VERDICT r4 #1) also PLANS the blocks on device — the per-chunk
    payload shrinks to five (NB,) scalars and the host's per-block
    numpy table building disappears entirely.  Identical verdicts by
    construction; parity-tested including the death rank.  "auto"
    (default) picks "device" on TPU and "full" elsewhere (on CPU the
    device IS the host's cores, so host-built tables win).

    `checkpoint_dir`: when set, the inter-chunk carry (member window,
    beam states, alive mask + the block cursor) is persisted there
    after every chunk call (~32k barriers), keyed by a digest of the
    packed history and every shape knob.  A later call on the same
    history resumes from the last completed chunk instead of block
    zero — SURVEY.md §5's "checkpoint long searches": a time-limited
    or killed analysis pass doesn't forfeit progress, `analyze`
    re-runs pick up where they stopped.  The file is removed when the
    search concludes (witness found or frontier died); only a
    budget-expiry exit leaves it behind.

    `width_hint` forces at least that window width so a warm-up run can
    pre-compile the kernels a bigger history will use (see plan_width).

    `pallas`: "auto" runs the easy sweep as a Pallas VMEM kernel on TPU
    backends and the XLA scan elsewhere; "on"/"interpret"/"off" force a
    mode ("interpret" is the CPU-testable emulation of the kernel).  A
    kernel that fails to build or run raises: nothing reruns it on the
    scan.  `bars_per_block` beyond what SMEM holds
    (`pallas_smem_bytes`) raises ValueError before the kernel is built.

    `rank_override`: optional (n,) int array giving NON-barrier rows a
    synthetic barrier rank (-1 = no override).  Once that rank passes,
    the row behaves like a retired barrier: implied membership,
    excluded from helper candidacy, dropped from later windows.  The
    key-concatenated stream checker (ops/wgl_stream.py) uses this to
    fence each key's indeterminate ops inside its own segment.
    Checkpointing is disabled under an override (the checkpoint key
    does not cover it).

    `out_info`: optional dict the search fills with diagnostics — on
    failure, "died_at_rank" is the global rank of the first barrier
    the chain search could not linearize (None if the death point was
    not localized).

    With telemetry on, counter `wgl.witness.chain-rounds` grows by the
    chain rounds the search ran.
    """
    import jax
    import jax.numpy as jnp

    t0 = time.monotonic()
    n = packed.n
    if n == 0 or packed.n_ok == 0:
        return WGLResult(valid=True, configs_explored=1,
                         elapsed_s=time.monotonic() - t0)

    if bars_per_block is None or blocks_per_call is None:
        # Chunk-shape buckets are profile-chosen (ROADMAP item 1 (c)):
        # the trained cost model ranks the bucket grid when its witness
        # predictor covers the candidates, else the measured heuristic
        # default.  Explicit caller values always win.
        from ..plan.costmodel import choose_witness_block_knobs

        knobs, source = choose_witness_block_knobs(n, int(packed.n_ok))
        if bars_per_block is None:
            bars_per_block = knobs["bars_per_block"]
        if blocks_per_call is None:
            blocks_per_call = knobs["blocks_per_call"]
        telemetry.count(f"wgl.plan.witness-block-{source}")
    # Record the resolved shape on the enclosing pass capture so the
    # cost model can train on what actually ran.
    profile.annotate(bars_per_block=int(bars_per_block),
                     blocks_per_call=int(blocks_per_call))

    if rank_override is not None:
        checkpoint_dir = None  # ckpt key does not cover the override
    try:
        with telemetry.span("wgl.witness.plan", n=n):
            bars, bar_rank, inv32, ret32, blocks, _ = _plan_blocks(
                packed, bars_per_block, info_window, rank_override
            )
    except OverflowError:
        # Timeline past int32 (e.g. a huge concatenated stream): the
        # witness tier can't represent it — escalate, don't crash.
        return None
    n_bars = len(bars)
    if max(len(a) for _, _, a in blocks) > max_window:
        return None

    SW = pm.state_width
    B = _bucket(beam, lo=8)
    K = bars_per_block
    packed_on = packed_enabled(packed_lanes)
    if len(blocks) < blocks_per_call:
        # Short histories (one chunk): trim the call width to a
        # bucket of the real block count — padding blocks are no-ops
        # semantically but still cost K scan iterations each, which
        # DOMINATES small searches (measured on the 200-key stream:
        # 22 padding blocks of 32 ≈ 2x the real barrier work).
        blocks_per_call = _bucket(len(blocks), lo=4)
    D = depth
    NB = blocks_per_call
    W = _bucket(max(max(len(a) for _, _, a in blocks), width_hint, 1))
    if telemetry.enabled():
        telemetry.gauge("wgl.witness.window", W)
        telemetry.gauge("wgl.witness.beam", B)
        telemetry.gauge("wgl.witness.blocks", len(blocks))
        if packed_on:
            telemetry.count("wgl.packed.witness-runs")

    if pallas not in ("auto", "on", "off", "interpret"):
        raise ValueError(f"unknown pallas mode {pallas!r}")
    requested = pallas
    if pallas == "auto":
        pallas = "on" if jax.devices()[0].platform == "tpu" else "off"
    if pm.jax_step_rows is None or B > 32:
        # No Mosaic-safe batched step for this model, or the beam no
        # longer fits the kernel's one-word member bit-packing.
        if requested == "on":
            raise ValueError(
                "pallas='on' needs a model with jax_step_rows and a beam "
                f"of at most 32 lanes (beam bucket {B})"
            )
        pallas = "off"
    if pallas != "off" and pallas_smem_bytes(K, W) > PALLAS_SMEM_BYTES:
        raise ValueError(
            f"bars_per_block={K} at window {W} needs "
            f"{pallas_smem_bytes(K, W)} bytes of SMEM for the Pallas "
            f"sweep; the chip has {PALLAS_SMEM_BYTES}"
        )
    telemetry.count(f"wgl.witness.pallas-{pallas}")

    if transfer not in ("auto", "full", "indices", "device"):
        raise ValueError(f"unknown transfer mode {transfer!r}")
    if transfer == "auto":
        # On the TPU, planning on device removes the per-chunk H2D
        # (~0.7-2 MB) and the host's per-block numpy table building
        # (~0.35 s at 100k ops); on CPU the device IS the host's cores,
        # so shipping host-built tables is faster (0.46 s vs 0.91 s
        # best-of-4 on the 100k config).
        transfer = ("device" if jax.devices()[0].platform == "tpu"
                    else "full")
    if transfer == "device" and rank_override is not None:
        # Device planning derives is_info from rank == NO_BAR, which
        # an override breaks; the stream path's payloads are small
        # anyway.  Indices mode keeps the once-uploaded-tables win.
        transfer = "indices"

    dev_slice = 0
    dev_plan = None
    if transfer == "device":
        # Per-block scalars the device planner consumes — all derived
        # from the plan the host already built.  hi = first row not
        # yet invoked at the block's last barrier; lo = the window's
        # first row; S buckets the widest (lo, hi) span.
        nblk_all = len(blocks)
        k0_all = np.empty(nblk_all, dtype=np.int32)
        er_all = np.empty(nblk_all, dtype=np.int32)
        lo_all = np.empty(nblk_all, dtype=np.int32)
        nb_all = np.empty(nblk_all, dtype=np.int32)
        cut_all = np.full(nblk_all, np.iinfo(np.int32).min,
                          dtype=np.int32)
        icum_host = np.cumsum(packed.status != ST_OK).astype(np.int32)
        span_max = 1
        for bi, (k0, block_bars, active) in enumerate(blocks):
            er = int(ret32[block_bars[-1]])
            hi = int(np.searchsorted(inv32, np.int32(er), side="left"))
            lo = int(active[0]) if len(active) else hi
            k0_all[bi] = k0
            er_all[bi] = er
            lo_all[bi] = lo
            nb_all[bi] = len(block_bars)
            if info_window is not None and hi > 0:
                cut_all[bi] = int(icum_host[hi - 1]) - info_window
            span_max = max(span_max, hi - lo)
        dev_slice = _bucket(span_max, lo=min(W, 1024))
        dev_plan = (k0_all, er_all, lo_all, nb_all, cut_all, icum_host)

    def _retry_smaller(e: BaseException):
        """Degradation-ladder fallback for device resource exhaustion
        (XLA RESOURCE_EXHAUSTED / compile failure / injected fault):
        first shed the packed lanes (an optimisation, not a budget),
        then retry ONCE with a halved block plan — the chunk call's
        working set scales with bars_per_block × blocks_per_call —
        then escalate (return None) so the caller falls through to the
        next tier.  Every caller-visible kwarg is reproduced here —
        keep it that way so a new parameter can't be silently dropped
        on a retry."""
        import logging

        if packed_on:
            degrade.record("witness", "packed-fallback", e)
            telemetry.count("wgl.packed.fallbacks")
            if time_limit_s is not None:
                rem = time_limit_s - (time.monotonic() - t0)
                if rem <= 0:
                    return None
            else:
                rem = None
            return check_wgl_witness(
                packed, pm, beam=beam, bars_per_block=bars_per_block,
                blocks_per_call=blocks_per_call, depth=depth,
                info_window=info_window, max_window=max_window,
                width_hint=width_hint, time_limit_s=rem,
                pallas=pallas,
                checkpoint_dir=checkpoint_dir, transfer=transfer,
                rank_override=rank_override, out_info=out_info,
                packed_lanes=False, _degraded=_degraded,
            )
        if _degraded or bars_per_block <= 64:
            degrade.record("witness", "fall-through", e)
            logging.getLogger(__name__).warning(
                "witness tier out of device resources even after "
                "halving; escalating to the next tier", exc_info=True,
            )
            return None
        degrade.record("witness", "retry-halved", e)
        logging.getLogger(__name__).warning(
            "witness chunk call exhausted device resources; retrying "
            "once at bars_per_block=%d", bars_per_block // 2,
            exc_info=True,
        )
        if time_limit_s is not None:
            remaining = time_limit_s - (time.monotonic() - t0)
            if remaining <= 0:
                return None
        else:
            remaining = None
        return check_wgl_witness(
            packed, pm, beam=beam, bars_per_block=bars_per_block // 2,
            blocks_per_call=max(blocks_per_call // 2, 1), depth=depth,
            info_window=info_window, max_window=max_window,
            width_hint=width_hint, time_limit_s=remaining,
            pallas=pallas,
            checkpoint_dir=checkpoint_dir, transfer=transfer,
            rank_override=rank_override, out_info=out_info,
            packed_lanes=packed_on, _degraded=True,
        )

    # The step fn itself keys the cache (strong ref): an id() key
    # can collide after GC address reuse and serve the wrong
    # model's transition kernel.
    key = (B, W, SW, K, D, NB, pm.jax_step, pallas, packed_on)
    # jax.jit is lazy: a freshly built chunk fn actually compiles on
    # its FIRST call — the trace labels that call "compile".
    fresh_fn = False
    fns = _chunk_fn_cache.get(key)
    if fns is None:
        fresh_fn = True
        fns = _make_chunk_fn(B, W, SW, K, D, NB, pm.jax_step,
                             pallas_mode=pallas,
                             jax_step_rows=pm.jax_step_rows,
                             packed=packed_on)
        _chunk_fn_cache[key] = fns
    fn, fn_idx, make_dev = fns
    fn_dev = None
    if transfer == "device":
        dev_key = (key, dev_slice)
        fn_dev = _chunk_dev_cache.get(dev_key)
        if fn_dev is None:
            fresh_fn = True  # new device-planner entry compiles too
            fn_dev = make_dev(dev_slice)
            _chunk_dev_cache[dev_key] = fn_dev

    row_tables = None
    prev_act_dev = None
    if transfer in ("indices", "device"):
        # One upload per check; subsequent chunk calls pass these
        # already-resident arrays, which jit does NOT re-transfer.
        # Tables pad to a bucket of n so that histories of nearby
        # lengths share one compiled chunk fn (the stream witness
        # checks many key spans per cohort; unpadded, every span
        # length compiled anew — ~30 compiles for a 200-key cohort).
        # Padding rows are never selected: the device planner masks
        # rows >= n_total and the index modes mask by nw/nb.
        dev = jax.devices()[0]
        n_rows = _bucket(n, lo=1024)

        def rows(a, fill):
            out = np.full(n_rows, fill, dtype=np.int32)
            out[:n] = a
            return jax.device_put(out, dev)

        row_tables = tuple(
            rows(a, fill) for a, fill in (
                (packed.f, 0), (packed.a0, 0), (packed.a1, 0),
                (ret32, INF), (inv32, INF),
                (np.minimum(bar_rank, NO_BAR), NO_BAR),
            )
        )
        if telemetry.enabled():
            telemetry.count("wgl.h2d-bytes",
                            sum(int(a.nbytes) for a in row_tables))
    if transfer == "device":
        # Device planning extras: the info cumsum (retention rule),
        # the barrier array (padded so any k0 slice is in bounds),
        # and the carried previous-window rows.
        icumA = rows(dev_plan[5], dev_plan[5][-1])
        bars_pad = np.zeros(_bucket(len(bars) + K, lo=K),
                            dtype=np.int32)
        bars_pad[: len(bars)] = bars
        barsA = jax.device_put(bars_pad, dev)
        prev_act_dev = jnp.asarray(
            np.full(W, packed.n, dtype=np.int32)
        )

    member = jnp.zeros((W, B), dtype=bool)
    states = jnp.tile(
        jnp.asarray(np.asarray(pm.init_state, dtype=np.int32)), (B, 1)
    )
    alive_np = np.zeros(B, dtype=bool)
    alive_np[0] = True
    alive = jnp.asarray(alive_np)
    failed = jnp.bool_(False)

    identity_perm = np.arange(W, dtype=np.int32)
    prev_active: Optional[np.ndarray] = None

    ckpt_path = ckpt_key = None
    c0_start = 0
    if checkpoint_dir is not None:
        ckpt_key = _ckpt_key(packed, pm, B, W, SW, K, NB, info_window)
        # The key prefix in the filename keeps CONCURRENT searches
        # sharing one dir (per-key checks under IndependentChecker's
        # thread pool all get the same opts["dir"]) from clobbering —
        # or tearing — each other's files.
        ckpt_path = os.path.join(
            checkpoint_dir, f"wgl-witness-{ckpt_key[:16]}.ckpt.npz"
        )
        saved = _ckpt_load(ckpt_path, ckpt_key)
        if saved is not None:
            c0_start, member_np, states_np, alive_np2 = saved
            member = jnp.asarray(member_np)
            states = jnp.asarray(states_np)
            alive = jnp.asarray(alive_np2)
            # The resumed chunk's first re-gather keys off the LAST
            # block of the chunk before it; blocks are recomputed
            # deterministically from the packed history, so only the
            # cursor needed saving.  A cursor past the end (the last
            # chunk saved c0 + NB > len) clamps: the loop is skipped
            # and the final alive check concludes from the carry.
            c0_start = min(c0_start, len(blocks))
            if c0_start > 0:
                prev_active = blocks[c0_start - 1][2]
                if transfer == "device":
                    pa = np.full(W, packed.n, dtype=np.int32)
                    pa[: len(prev_active)] = prev_active
                    prev_act_dev = jnp.asarray(pa)

    for c0 in range(c0_start, len(blocks), NB):
        chunk_blocks = blocks[c0 : c0 + NB]
        nblk = len(chunk_blocks)
        if transfer == "device":
            # Five (NB,) scalars per chunk; everything else is planned
            # on device from the resident tables.  Only the call
            # differs from the other modes: the try/except and the
            # post-chunk tail below are shared.
            k0_all, er_all, lo_all, nb_all, cut_all, _ = dev_plan

            def padded(a, fill=0):
                out = np.full(NB, fill, dtype=np.int32)
                out[:nblk] = a[c0 : c0 + nblk]
                return out

            dev_args = (
                jnp.asarray(padded(k0_all)),
                jnp.asarray(padded(er_all)),
                jnp.asarray(padded(lo_all)),
                jnp.asarray(padded(nb_all)),
                jnp.asarray(padded(cut_all, np.iinfo(np.int32).min)),
            )
        else:
            perm_np = np.tile(identity_perm, (NB, 1))
            present_np = np.ones((NB, W), dtype=bool)
            k0s_np = np.zeros(NB, dtype=np.int32)
            if transfer == "indices":
                # Per-chunk payload: row-INDEX arrays only; the tables
                # are rebuilt on device from the once-uploaded
                # row_tables.
                bar_idx_np = np.zeros((NB, K), dtype=np.int32)
                act_idx_np = np.full((NB, W), packed.n, dtype=np.int32)
                nbars_np = np.zeros(NB, dtype=np.int32)
                nws_np = np.zeros(NB, dtype=np.int32)
            else:
                bars_np = np.zeros((NB, 6, K), dtype=np.int32)
                bars_np[:, 1, :] = INF
                tab_np = np.zeros((NB, 5, W), dtype=np.int32)

            for bi, (k0, block_bars, active) in enumerate(chunk_blocks):
                nw = len(active)
                nb = len(block_bars)
                k0s_np[bi] = k0
                if transfer == "indices":
                    bar_idx_np[bi, :nb] = block_bars
                    act_idx_np[bi, :nw] = active
                    nbars_np[bi] = nb
                    nws_np[bi] = nw
                else:
                    bars_np[bi, 0, :nb] = np.searchsorted(active,
                                                          block_bars)
                    bars_np[bi, 1, :nb] = ret32[block_bars]
                    bars_np[bi, 2, :nb] = 1
                    bars_np[bi, 3, :nb] = packed.f[block_bars]
                    bars_np[bi, 4, :nb] = packed.a0[block_bars]
                    bars_np[bi, 5, :nb] = packed.a1[block_bars]
                    row = tab_np[bi]
                    row[0, :] = INF
                    row[0, :nw] = inv32[active]
                    row[1, :nw] = packed.f[active]
                    row[2, :nw] = packed.a0[active]
                    row[3, :nw] = packed.a1[active]
                    row[4, :] = NO_BAR
                    row[4, :nw] = np.minimum(bar_rank[active], NO_BAR)
                if prev_active is None:
                    # Very first block: nothing to re-gather; member
                    # is all-False already, so a full wipe is a no-op.
                    present_np[bi, :] = False
                    perm_np[bi, :] = 0
                else:
                    perm, present = window_regather(prev_active, active)
                    perm_np[bi, :nw] = perm
                    perm_np[bi, nw:] = 0
                    present_np[bi, :nw] = present
                    present_np[bi, nw:] = False
                prev_active = active

        if telemetry.enabled():
            if transfer == "device":
                h2d = sum(int(a.nbytes) for a in dev_args) + 4
            elif transfer == "indices":
                h2d = sum(int(a.nbytes) for a in (
                    bar_idx_np, act_idx_np, nbars_np, nws_np,
                    perm_np, present_np, k0s_np))
            else:
                h2d = sum(int(a.nbytes) for a in (
                    bars_np, tab_np, perm_np, present_np, k0s_np))
            telemetry.count("wgl.h2d-bytes", h2d)
            telemetry.count("wgl.witness.chunks", 1)
            sp = telemetry.span(
                "wgl.witness.compile" if fresh_fn
                else "wgl.witness.chunk", transfer=transfer)
        else:
            sp = telemetry.span("")  # shared no-op
        fresh_fn = False
        try:
            degrade.maybe_fault("witness")
            # The span covers dispatch AND the bool(failed) sync, so
            # its duration is real device time, not async enqueue.
            with sp:
                if transfer == "device":
                    (member, states, alive, failed, died, rounds,
                     prev_act_dev) = fn_dev(
                        member, states, alive, failed, prev_act_dev,
                        *dev_args, jnp.int32(packed.n),
                        *row_tables, icumA, barsA,
                    )
                elif transfer == "indices":
                    member, states, alive, failed, died, rounds = fn_idx(
                        member, states, alive, failed,
                        jnp.asarray(bar_idx_np), jnp.asarray(act_idx_np),
                        jnp.asarray(nbars_np), jnp.asarray(nws_np),
                        jnp.asarray(perm_np), jnp.asarray(present_np),
                        jnp.asarray(k0s_np), *row_tables,
                    )
                else:
                    member, states, alive, failed, died, rounds = fn(
                        member, states, alive, failed,
                        jnp.asarray(bars_np), jnp.asarray(tab_np),
                        jnp.asarray(perm_np), jnp.asarray(present_np),
                        jnp.asarray(k0s_np),
                    )
                # One sync per chunk (~32k barriers): early exit + time
                # budget.  The sync ALSO belongs inside the try — jitted
                # dispatch is asynchronous, so execution-time failures
                # only raise when a result is consumed.
                failed_now = bool(failed)
            if telemetry.enabled():
                telemetry.count("wgl.witness.chain-rounds", int(rounds))
        except Exception as e:
            if degrade.is_resource_error(e):
                # The device (not the search) gave out: degradation
                # ladder — evict the possibly-huge compiled entry, retry
                # once halved, then escalate to the next tier.
                _chunk_fn_cache.pop(key, None)
                _chunk_dev_cache.pop((key, dev_slice), None)
                return _retry_smaller(e)
            raise
        if failed_now:
            _ckpt_remove(ckpt_path)  # concluded: a resume can't help
            if out_info is not None:
                d = int(died)
                out_info["died_at_rank"] = d if d != int(NO_BAR) else None
            return None
        budget_blown = (time_limit_s is not None
                        and time.monotonic() - t0 > time_limit_s)
        if ckpt_path is not None and (
            budget_blown or time.monotonic() - t0 > CKPT_MIN_ELAPSED_S
        ):
            _ckpt_save(ckpt_path, ckpt_key, c0 + NB,
                       np.asarray(member), np.asarray(states),
                       np.asarray(alive))
        if budget_blown:
            return None  # budget blown: the checkpoint stays for resume

    _ckpt_remove(ckpt_path)
    if not bool(alive.any()):
        if out_info is not None:
            out_info["died_at_rank"] = None  # not localized
        return None
    return WGLResult(
        valid=True,
        configs_explored=n_bars,
        elapsed_s=time.monotonic() - t0,
    )
