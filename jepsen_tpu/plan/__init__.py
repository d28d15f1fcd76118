"""Checking-plan IR: compile histories to pass DAGs, execute them once.

The checker tiers (witness / stream / frontier / batched / BFS /
settle / exact-CPU, plus the elle SCC path) are pass families, and the
callers — `Linearizable`, `IndependentChecker`, the checkerd scheduler
— reach the engines only through a compiled plan, so the ladder's order
and its special cases live in one place.  This package is the
compile-then-execute split (TVM's architecture, PAPERS.md) applied to
checking:

  * `ir.py`        — `PassFamily` declarations (soundness direction,
                     resource class) and `PassNode`/`Plan` DAGs with
                     typed fallback edges
  * `compiler.py`  — packed cohort + model + budget -> `Plan`; the
                     existing engines are registered as pass families
                     instead of hard-coded ladder rungs
  * `executor.py`  — one engine runs any plan under the existing
                     budget / degradation / profile.capture machinery,
                     fusing compatible passes across keys and runs and
                     memoizing per plan node
  * `costmodel.py` — a featurized regressor trained offline from
                     profiles.jsonl (`tools/costmodel_train.py`) picks
                     knobs; the hand heuristics are the explicit
                     untrained fallback
  * `cache.py`     — persistent plan memo (store/format.py framing) +
                     JAX's on-disk compilation cache, so fresh
                     processes and restarted daemons skip recompilation

The persistent caches activate only when `JEPSEN_PLAN_CACHE=<dir>` (or
`checkerd --plan-cache`) names a directory — in-memory behavior is
byte-identical either way.
"""

from __future__ import annotations

#: Persistent cache directory (plan memo + XLA compile cache); unset
#: means no on-disk state.
CACHE_ENV = "JEPSEN_PLAN_CACHE"


from .ir import (  # noqa: E402
    Plan,
    PassFamily,
    PassNode,
    family,
    known_families,
    register_family,
)

__all__ = [
    "CACHE_ENV",
    "Plan",
    "PassFamily",
    "PassNode",
    "family",
    "known_families",
    "register_family",
]
