"""The result line, and the comparisons as the last lines of stderr."""

from __future__ import annotations

import json
import sys


def emit(out: dict) -> None:
    sys.stdout.flush()
    for name, c in out["checks"].items():
        print(f"compare {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
