"""Persistent plan memo + XLA compile cache.

Two layers, activated by one directory (`JEPSEN_PLAN_CACHE=<dir>` or
`checkerd --plan-cache <dir>`; no directory = no on-disk state, the
in-memory settle memo behaves exactly as before):

* **Plan memo** — `plan-memo.jtpu`, an append-only journal of settled
  plan-node verdicts in store/format.py framing (`BLOCK_PLAN` blocks).
  The key is `sha256(packed-digest | plan identity)` where the identity
  covers model key, algorithm, and budget — so changing any of those
  MISSES while a byte-identical resubmission HITS, and a restarted
  daemon re-checking the same history skips the whole settle ladder.
  Crash safety comes free from BlockWriter's torn-tail truncation.

* **XLA compile cache** — JAX's on-disk compilation cache, kept where
  `compile_cache.place()` puts it (`JAX_COMPILATION_CACHE_DIR`, else
  `<repo>/.jax_cache`) and never under `<dir>`; activation only drops
  the size and compile-time thresholds so every kernel is cached.

Only *decisive, sanitized* verdicts may be journaled: callers strip
positional certificates (final-configs, crashed-op, counterexample
files) before `put`, the same rule the in-memory settle memo enforces.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import threading
import time
from typing import Any, Optional

from .. import telemetry
from ..store import format as fmt

log = logging.getLogger(__name__)

MEMO_FILE = "plan-memo.jtpu"

#: Journal entries larger than this are not memoized — a plan memo is a
#: verdict cache, not a certificate store.
MAX_ENTRY_BYTES = 1 << 20


def memo_key(digest: str, identity: dict) -> str:
    """Cache key for one settled unit of work.  `digest` is the packed
    subhistory digest (independent._settle_digest / checkerd pack
    digest); `identity` carries every plan knob that must invalidate:
    model key, algorithm, budget, plan fingerprint."""
    blob = json.dumps(identity, sort_keys=True, separators=(",", ":"),
                      default=repr)
    return hashlib.sha256(f"{digest}|{blob}".encode()).hexdigest()


class PlanMemo:
    """The journaled verdict memo.  Thread-safe; one instance per
    process per cache directory."""

    def __init__(self, path: str):
        self.path = path
        self._lock = threading.Lock()
        self._mem: dict[str, dict] = {}
        self.hits = 0
        self.misses = 0
        self.puts = 0
        self.loaded = 0
        self._writer: Optional[fmt.BlockWriter] = None
        self._load()

    def _load(self) -> None:
        """Replays the journal (last write per key wins).  The
        BlockWriter constructor below re-validates and truncates any
        torn tail before we append."""
        if os.path.exists(self.path):
            try:
                with open(self.path, "rb") as f:
                    if f.read(len(fmt.MAGIC)) == fmt.MAGIC:
                        size = os.path.getsize(self.path)
                        while True:
                            rec = fmt._read_block(f, size)
                            if rec is None:
                                break
                            _, btype, payload = rec
                            if btype != fmt.BLOCK_PLAN:
                                continue
                            k = payload.get("k")
                            v = payload.get("v")
                            if isinstance(k, str) and isinstance(v, dict):
                                self._mem[k] = v
            except OSError as e:
                log.warning("plan memo %s unreadable: %r", self.path, e)
        self.loaded = len(self._mem)
        self._writer = fmt.BlockWriter(self.path)

    def get(self, key: str) -> Optional[dict]:
        with self._lock:
            v = self._mem.get(key)
            if v is None:
                self.misses += 1
                telemetry.count("wgl.plan.memo-miss")
                return None
            self.hits += 1
        telemetry.count("wgl.plan.memo-hit")
        return json.loads(json.dumps(v))  # caller-owned copy

    def put(self, key: str, verdict: dict) -> None:
        entry = {"k": key, "v": verdict, "ts": round(time.time(), 3)}
        try:
            blob = json.dumps(verdict, default=repr)
        except (TypeError, ValueError):
            return
        if len(blob) > MAX_ENTRY_BYTES:
            telemetry.count("wgl.plan.memo-oversize")
            return
        with self._lock:
            if key in self._mem:
                return
            self._mem[key] = json.loads(json.dumps(verdict, default=repr))
            self.puts += 1
            if self._writer is not None:
                try:
                    self._writer.append(fmt.BLOCK_PLAN, entry)
                    self._writer.sync()
                except OSError as e:
                    log.warning("plan memo append failed: %r", e)
        telemetry.count("wgl.plan.memo-store")

    def __len__(self) -> int:
        with self._lock:
            return len(self._mem)

    def stats(self) -> dict:
        with self._lock:
            return {
                "path": self.path,
                "entries": len(self._mem),
                "loaded": self.loaded,
                "hits": self.hits,
                "misses": self.misses,
                "puts": self.puts,
            }

    def close(self) -> None:
        with self._lock:
            if self._writer is not None:
                self._writer.close()
                self._writer = None


# ---------------------------------------------------------------------------
# Process-wide activation
# ---------------------------------------------------------------------------

_lock = threading.Lock()
_memo: Optional[PlanMemo] = None
_dir: Optional[str] = None
_configured = False
#: The compile-cache directory once enable_xla_cache() ran.
_xla_dir: Optional[str] = None


def configure(cache_dir: Optional[str]) -> None:
    """Points the process at a cache directory (both layers), or at
    None to run purely in-memory.  checkerd's --plan-cache flag and the
    smoke tool call this; everyone else inherits JEPSEN_PLAN_CACHE."""
    global _memo, _dir, _configured
    with _lock:
        if _memo is not None:
            _memo.close()
        _memo = None
        _dir = cache_dir
        _configured = True
    if cache_dir:
        enable_xla_cache()


def cache_dir() -> Optional[str]:
    with _lock:
        if _configured:
            return _dir
    from . import CACHE_ENV

    return os.environ.get(CACHE_ENV) or None


def active_memo() -> Optional[PlanMemo]:
    """The process's plan memo, or None when no cache dir is set."""
    global _memo
    d = cache_dir()
    if not d:
        return None
    if not _xla_dir:
        # Env-var activation (JEPSEN_PLAN_CACHE with no configure()
        # call) must wire the compile cache too, not just the memo.
        enable_xla_cache()
    with _lock:
        if _memo is not None and _memo.path == os.path.join(d, MEMO_FILE):
            return _memo
        try:
            os.makedirs(d, exist_ok=True)
            _memo = PlanMemo(os.path.join(d, MEMO_FILE))
        except OSError as e:
            log.warning("plan cache dir %s unusable: %r", d, e)
            _memo = None
        return _memo


def enable_xla_cache() -> Optional[str]:
    """Turns JAX's persistent compilation cache on for every kernel
    (thresholds zeroed, so even the sub-second CPU kernels of the test
    suite land in it) in the directory `compile_cache.place()` picks.
    Idempotent; returns that directory."""
    global _xla_dir
    try:
        import jax

        from .. import compile_cache

        xdir = compile_cache.place()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        _xla_dir = xdir
        return xdir
    except Exception as e:  # jax missing: plan memo still works
        log.warning("XLA persistent cache unavailable: %r", e)
        return None


def xla_cache_files() -> int:
    """How many compiled executables the XLA cache holds — the smoke
    tool's 'no new compilations on run 2' probe."""
    if not _xla_dir:
        return 0
    try:
        return sum(1 for n in os.listdir(_xla_dir)
                   if not n.startswith("."))
    except OSError:
        return 0


def stats() -> dict:
    """Aggregate cache view for checkerd stats() and /fleet."""
    d = cache_dir()
    m = active_memo() if d else None
    return {
        "dir": d,
        "memo": m.stats() if m else None,
        "xla_files": xla_cache_files(),
        "xla_enabled": _xla_dir is not None,
    }


def reset_for_tests() -> None:
    """Drops process-wide cache state (tests re-point the cache dir
    between cases)."""
    global _memo, _dir, _configured
    with _lock:
        if _memo is not None:
            _memo.close()
        _memo = None
        _dir = None
        _configured = False
