"""Device degradation ladder support: classify XLA resource/compile
failures, inject them for tests, and record every degradation step.

The WGL tiers (witness → stream → batched → plain device BFS → CPU
exact) each catch resource exhaustion at their device-call sites, retry
once with a halved chunk/batch/beam, and otherwise fall through to the
next tier.  This module is the shared vocabulary: `is_resource_error`
decides what counts as "the device ran out, not the search", `record`
emits the `wgl.degrade.<tier>.<action>` telemetry counter AND appends
to the active capture so checkers can put the ladder in their result
metadata, and `maybe_fault`/JEPSEN_WGL_FAULT is the fault hook the
fault-matrix harness uses to force a tier failure without real
hardware (mirrors how DrJAX keeps host orchestration robust around
device-side JAX failures).
"""

from __future__ import annotations

import os
import threading
from typing import Any, Optional

from .. import telemetry
from ..telemetry import flight

#: Comma-separated tier names ("witness", "stream", "batched", "device"),
#: or "all": each named tier raises a synthetic RESOURCE_EXHAUSTED at its
#: device-call site, driving the ladder end-to-end on any backend.
FAULT_ENV = "JEPSEN_WGL_FAULT"

#: Message fragments that mean "the device/compiler gave out", as opposed
#: to a bug in the search itself.  Matched case-insensitively against the
#: stringified exception.
_RESOURCE_MARKERS = (
    "resource_exhausted",
    "resource exhausted",
    "out of memory",
    "ran out of memory",
    "oom",
    "allocation failure",
    "failed to allocate",
    "compilation failure",
    "xla compilation",
    "internal: failed to compile",
)


class InjectedFault(RuntimeError):
    """Raised by maybe_fault; message matches the resource markers so the
    production catch sites treat it exactly like a real device failure."""


def fault_tiers() -> set[str]:
    raw = os.environ.get(FAULT_ENV, "")
    return {t.strip() for t in raw.split(",") if t.strip()}


def maybe_fault(tier: str) -> None:
    """Raises a synthetic resource-exhaustion error when JEPSEN_WGL_FAULT
    names this tier (or "all").  Reads the env each call so tests can
    toggle tiers without reimporting; the lookup is two dict hits on a
    path that is about to launch a device program anyway."""
    tiers = fault_tiers()
    if tier in tiers or "all" in tiers:
        raise InjectedFault(
            f"RESOURCE_EXHAUSTED: injected fault for tier {tier!r} "
            f"({FAULT_ENV}={os.environ.get(FAULT_ENV)!r})"
        )


def is_resource_error(e: BaseException) -> bool:
    """True when the exception smells like XLA resource exhaustion or a
    compile failure — the class of errors the ladder may degrade on.
    Anything else (assertion, shape bug, keyboard interrupt) must
    propagate: degrading on a logic error would hide it."""
    if isinstance(e, (MemoryError, InjectedFault)):
        return True
    if isinstance(e, (KeyboardInterrupt, SystemExit)):
        return False
    # XlaRuntimeError lives in jaxlib internals; match by name so this
    # works across jaxlib layouts and on CPU-only builds.
    name = type(e).__name__
    msg = f"{name}: {e}".lower()
    if name == "XlaRuntimeError" and (
        "resource" in msg or "memory" in msg or "compil" in msg
    ):
        return True
    return any(m in msg for m in _RESOURCE_MARKERS)


# ---------------------------------------------------------------------------
# Degradation event capture
# ---------------------------------------------------------------------------

_tls = threading.local()


class capture:
    """Context manager collecting degradation events recorded on this
    thread, so a checker can attach the ladder's path to its result
    metadata:

        with degrade.capture() as steps:
            res = check_wgl_device(...)
        if steps:
            out["degradations"] = steps

    Captures nest: an inner capture sees only its own events; they are
    replayed into the outer capture on exit so nothing is lost."""

    def __enter__(self) -> list[dict]:
        self._outer = getattr(_tls, "events", None)
        _tls.events = []
        return _tls.events

    def __exit__(self, *exc) -> None:
        mine = _tls.events
        _tls.events = self._outer
        if self._outer is not None:
            self._outer.extend(mine)
        return None


def record(tier: str, action: str, error: Optional[Any] = None) -> None:
    """Records one degradation step: a `wgl.degrade.<tier>.<action>`
    telemetry counter plus an event in the active capture (if any)."""
    telemetry.count(f"wgl.degrade.{tier}.{action}")
    flight.note(f"degrade.{tier}.{action}")
    events = getattr(_tls, "events", None)
    if events is not None:
        ev = {"tier": tier, "action": action}
        if error is not None:
            ev["error"] = f"{type(error).__name__}: {error}" if isinstance(
                error, BaseException
            ) else str(error)
        events.append(ev)


# ---------------------------------------------------------------------------
# Chip health
# ---------------------------------------------------------------------------

#: The chip's health as the process found it, exported on /metrics as a
#: one-hot `jepsen_chip_health{state=...}` gauge and on the web fleet
#: page: "unprobed" until `note_backend()` runs, then "ok" (a TPU) or
#: "absent" (any other backend).
_chip_state = "unprobed"


def chip_state() -> str:
    """Returns the chip health `note_backend()` recorded: one of
    telemetry.CHIP_HEALTH_STATES."""
    return _chip_state


class ChipBusy(RuntimeError):
    """The TPU belongs to another process on this host."""


def note_backend() -> str:
    """Initializes JAX's backend and records once which one it found.
    Call it only in a process that owns the device — never in a
    supervisor whose children need the chip.  A chip another process
    holds fails here at once, as ChipBusy: libtpu lets one process at a
    time load it, and its lockfile is what enforces that."""
    global _chip_state
    if _chip_state == "unprobed":
        import jax

        try:
            platform = jax.devices()[0].platform
        except RuntimeError as e:
            if "lockfile" in str(e) or "already in use" in str(e):
                raise ChipBusy(
                    "the TPU is held by another process on this host: "
                    "one process per chip — stop that process (do not "
                    "remove libtpu's lockfile)"
                ) from e
            raise
        _chip_state = "ok" if platform == "tpu" else "absent"
    return _chip_state
