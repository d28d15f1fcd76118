"""Incremental frontier state for the online witness search.

The witness engine (ops/wgl_witness.py) already runs as a chunked scan
whose inter-chunk carry — member window, beam states, alive mask — IS a
frontier: every config alive after block b is a legal linearization
witness of the first (b+1)*K barriers.  `FrontierCarry` generalizes the
PR 3 stream witness into an *online* consumer: each `advance()` call
extends that carry over the barriers that have become decidable since
the last call, instead of restarting the search from op 0.

Soundness — which rows and barriers may be consumed mid-run
-----------------------------------------------------------

Let s be the builder's stable bound (history/packed.py PackedBuilder:
the minimum invocation event index over in-flight ops).  Two facts make
incremental consumption exact:

1. **Row-prefix stability.**  Rows with inv < s are final: every future
   row belongs either to an in-flight op (inv >= s) or to an op not yet
   invoked (inv >= the event counter >= s), so new rows only ever
   append AFTER the inv-sorted prefix.  Row indices, contents and order
   of the prefix never change — the carried window (row indices in
   `prev_active`) stays valid.

2. **Barrier-prefix stability.**  A barrier (ok row) with ret < s is
   final in the ret-sorted barrier order: any future completion gets an
   event index past every current one, and any in-flight op has
   inv >= s hence ret > s.  So the first `n_stable_bars` barriers —
   exactly those with ret < s — have final ranks, and a block whose K
   barriers are all stable has a final window too (its entrants are
   rows with inv < end_ret < s, all in the stable prefix).

`advance()` therefore processes only FULL blocks of K barriers whose
barriers all have ret < s.  Rows inside those windows whose own barrier
is still unstable carry a PROVISIONAL rank — but any such rank is
>= n_stable_bars, and inside a processed block (every k_rank <
n_stable_bars) the engine only tests `rank < k_rank` (implied
membership) and `rank >= k0` (window retention): both are decided
identically by the provisional and the final value.  Replanning on a
longer prefix is thus guaranteed to reproduce the already-processed
blocks bit-for-bit, which is why the carry composes across calls.

The window width W grows monotonically as the history lengthens; the
member matrix is re-embedded by padding False rows (window positions
past the previous width were never occupied), and the between-chunk
re-gather permutation only indexes positions < len(prev_active), so it
maps correctly after padding.

Death and fallback: a died frontier — or any planner/device error —
marks the carry dead.  Dead means "the witness cannot prove this
stream online"; the caller falls back to the ordinary post-hoc ladder
(whole-history recheck), so a death costs latency, never soundness.
"""

from __future__ import annotations

import logging
import time
from typing import Optional

import numpy as np

from .. import telemetry
from ..history.packed import ST_OK, PackedOps
from ..models.base import PackedModel
from ..ops.wgl import _bucket, window_regather
from ..ops.wgl_witness import (
    INF,
    NARROW_INFO_WINDOW,
    NO_BAR,
    _chunk_fn_cache,
    _make_chunk_fn,
    _plan_blocks,
)

log = logging.getLogger(__name__)


class FrontierCarry:
    """Carries the witness search's device state across stable-prefix
    snapshots of one packed stream.

    Lifecycle: `advance(packed, s)` after each ingest swap with the
    builder's current stable snapshot; `finalize(packed)` once with the
    finished pack.  finalize returns True when a witness linearization
    survives the whole stream (exact: the stream is linearizable) or
    None when the frontier died / overflowed / errored — escalate to
    the post-hoc engines, never report invalid from here.
    """

    def __init__(
        self,
        pm: PackedModel,
        *,
        beam: int = 8,
        bars_per_block: int = 1024,
        blocks_per_call: int = 8,
        depth: int = 5,
        info_window: Optional[int] = NARROW_INFO_WINDOW,
        max_window: int = 32768,
    ):
        self.pm = pm
        self.B = _bucket(beam, lo=8)
        self.K = bars_per_block
        self.NB = blocks_per_call
        self.D = depth
        self.info_window = info_window
        self.max_window = max_window

        self.dead = False
        self.dead_reason: Optional[str] = None
        self.blocks_done = 0
        self.bars_done = 0
        self.chunks = 0
        self.device_s = 0.0

        self._W = 0
        self._member = None      # (W, B) bool device array
        self._states = None      # (B, SW) i32
        self._alive = None       # (B,) bool
        self._prev_active: Optional[np.ndarray] = None

    # -- internals ----------------------------------------------------------

    def _die(self, reason: str) -> None:
        self.dead = True
        self.dead_reason = reason
        # Free the device carry eagerly; a dead frontier never resumes.
        self._member = self._states = self._alive = None
        telemetry.count("wgl.online.frontier-deaths")
        log.info("online frontier died: %s (after %d blocks)",
                 reason, self.blocks_done)

    def _ensure_width(self, W: int) -> None:
        """Grows the window bucket, re-embedding the carried member
        matrix by padding False rows (positions past the old width were
        never occupied)."""
        import jax.numpy as jnp

        if W <= self._W:
            return
        if self._member is not None:
            old = np.asarray(self._member)
            grown = np.zeros((W, self.B), dtype=bool)
            grown[: old.shape[0]] = old
            self._member = jnp.asarray(grown)
        self._W = W

    def _init_carry(self) -> None:
        import jax.numpy as jnp

        self._member = jnp.zeros((self._W, self.B), dtype=bool)
        self._states = jnp.tile(
            jnp.asarray(np.asarray(self.pm.init_state, dtype=np.int32)),
            (self.B, 1),
        )
        alive_np = np.zeros(self.B, dtype=bool)
        alive_np[0] = True
        self._alive = jnp.asarray(alive_np)

    def _chunk_fn(self):
        """The compiled NB-block chunk entry for the current width.
        Shares ops/wgl_witness.py's cache (same key scheme) so a
        post-hoc witness run at the same shape reuses the compile."""
        W = self._W
        key = (self.B, W, self.pm.state_width, self.K, self.D, self.NB,
               self.pm.jax_step, "off", False)
        fns = _chunk_fn_cache.get(key)
        if fns is None:
            fns = _make_chunk_fn(
                self.B, W, self.pm.state_width, self.K, self.D, self.NB,
                self.pm.jax_step, pallas_mode="off",
                jax_step_rows=self.pm.jax_step_rows,
            )
            _chunk_fn_cache[key] = fns
        return fns[0]  # transfer="full" entry

    def _run_blocks(self, packed: PackedOps, blocks, ret32, inv32,
                    bar_rank, upto: int) -> bool:
        """Runs blocks [blocks_done, upto) through the chunk fn,
        chaining the carry.  Returns False when the frontier died
        (carry marked dead)."""
        import jax.numpy as jnp

        if upto <= self.blocks_done:
            return True
        W_need = _bucket(max(
            self._W, 1,
            max(len(a) for _, _, a in blocks[self.blocks_done:upto]),
        ))
        if W_need > self.max_window:
            self._die(f"window {W_need} exceeds max {self.max_window}")
            return False
        self._ensure_width(W_need)
        if self._member is None:
            self._init_carry()
        fn = self._chunk_fn()
        W, B, K, NB = self._W, self.B, self.K, self.NB
        identity_perm = np.arange(W, dtype=np.int32)
        prev_active = self._prev_active
        failed = jnp.bool_(False)
        member, states, alive = self._member, self._states, self._alive

        for c0 in range(self.blocks_done, upto, NB):
            chunk_blocks = blocks[c0: min(c0 + NB, upto)]
            # Host tables, transfer="full" (the streaming pipeline runs
            # host-adjacent; pre-gathered tables are the fast path on
            # CPU and fine over PCIe).
            bars_np = np.zeros((NB, 6, K), dtype=np.int32)
            bars_np[:, 1, :] = INF
            tab_np = np.zeros((NB, 5, W), dtype=np.int32)
            perm_np = np.tile(identity_perm, (NB, 1))
            present_np = np.ones((NB, W), dtype=bool)
            k0s_np = np.zeros(NB, dtype=np.int32)
            for bi, (k0, block_bars, active) in enumerate(chunk_blocks):
                nw = len(active)
                nb = len(block_bars)
                k0s_np[bi] = k0
                bars_np[bi, 0, :nb] = np.searchsorted(active, block_bars)
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
                    present_np[bi, :] = False
                    perm_np[bi, :] = 0
                else:
                    perm, present = window_regather(prev_active, active)
                    perm_np[bi, :nw] = perm
                    perm_np[bi, nw:] = 0
                    present_np[bi, :nw] = present
                    present_np[bi, nw:] = False
                prev_active = active

            t0 = time.monotonic()
            try:
                with telemetry.span("wgl.online.chunk",
                                    blocks=len(chunk_blocks)):
                    member, states, alive, failed, died, rounds = fn(
                        member, states, alive, failed,
                        jnp.asarray(bars_np), jnp.asarray(tab_np),
                        jnp.asarray(perm_np), jnp.asarray(present_np),
                        jnp.asarray(k0s_np),
                    )
                    failed_now = bool(failed)
                if telemetry.enabled():
                    telemetry.count("wgl.witness.chain-rounds",
                                    int(rounds))
            except Exception as e:  # noqa: BLE001
                # Any device/compile failure mid-run: mark dead and let
                # the post-hoc ladder (with its own degradation rungs)
                # decide the stream.  Online checking must never cost
                # the verdict.
                self._die(f"device error: {type(e).__name__}: {e}")
                return False
            self.device_s += time.monotonic() - t0
            self.chunks += 1
            telemetry.count("wgl.online.chunks")
            self.blocks_done = c0 + len(chunk_blocks)
            self.bars_done = sum(len(b[1]) for b in blocks[:self.blocks_done])
            self._prev_active = prev_active
            if failed_now:
                self._die("frontier died (witness cannot prove)")
                return False

        self._member, self._states, self._alive = member, states, alive
        return True

    def _plan(self, packed: PackedOps):
        try:
            return _plan_blocks(packed, self.K, self.info_window)
        except OverflowError:
            self._die("timeline exceeds int32")
            return None

    # -- API ----------------------------------------------------------------

    def rebase(self, rows_dropped: int, bars_dropped: int) -> None:
        """Shifts the carry after the builder discarded a stable prefix
        (PackedBuilder.discard_stable_prefix): row indices fall by
        `rows_dropped`, barrier ranks by `bars_dropped`.  Sound because
        the discard conditions guarantee (a) dropped rows are a
        row-index prefix with the lowest `bars_dropped` barrier ranks,
        so every retained rank/index shifts uniformly, (b) at least the
        most recent processed block stays resident, so the carried
        window (`_prev_active`) references only retained rows — the
        device-side member/states/alive arrays hold no row indices or
        event values and carry over untouched."""
        if self.dead or rows_dropped <= 0:
            return
        if bars_dropped % self.K != 0:
            self._die(
                f"rebase of {bars_dropped} bars misaligned to K={self.K}"
            )
            return
        blocks_gone = bars_dropped // self.K
        if blocks_gone >= self.blocks_done:
            self._die(
                f"rebase would drop {blocks_gone} of "
                f"{self.blocks_done} processed blocks"
            )
            return
        self.blocks_done -= blocks_gone
        self.bars_done -= bars_dropped
        if self._prev_active is not None:
            if self._prev_active.size and int(self._prev_active.min()) < rows_dropped:
                self._die("rebase dropped a row still in the carry window")
                return
            self._prev_active = self._prev_active - rows_dropped
        telemetry.count("wgl.online.rebase")
        telemetry.count("wgl.online.rebase-bars", bars_dropped)

    def advance(self, packed: PackedOps, s: int) -> None:
        """Consumes the newly decidable barriers of a stable-prefix
        snapshot (`packed`, stable bound `s` — see PackedBuilder).
        Only FULL blocks whose K barriers all have ret < s run; the
        rest wait for the next call or finalize()."""
        if self.dead or packed.n == 0 or packed.n_ok == 0:
            return
        with telemetry.span("wgl.online.advance", rows=packed.n):
            plan = self._plan(packed)
            if plan is None:
                return
            bars, bar_rank, inv32, ret32, blocks, _ = plan
            n_stable_bars = int(np.count_nonzero(
                (packed.status == ST_OK) & (packed.ret < s)
            ))
            upto = min(n_stable_bars // self.K, len(blocks))
            self._run_blocks(packed, blocks, ret32, inv32, bar_rank, upto)

    def finalize(self, packed: PackedOps) -> Optional[bool]:
        """Runs the remaining blocks over the FINISHED pack and
        concludes: True = a witness survives (the stream is proven
        linearizable), None = escalate post-hoc."""
        if self.dead:
            return None
        if packed.n == 0 or packed.n_ok == 0:
            return True  # no barriers: trivially linearizable
        plan = self._plan(packed)
        if plan is None:
            return None
        bars, bar_rank, inv32, ret32, blocks, _ = plan
        if not self._run_blocks(packed, blocks, ret32, inv32, bar_rank,
                                len(blocks)):
            return None
        if self._alive is None or not bool(self._alive.any()):
            self._die("frontier empty at finalize")
            return None
        return True
