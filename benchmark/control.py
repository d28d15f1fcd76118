#!/usr/bin/env python3
"""Runs one cell with its control in the program's place.

    python3 benchmark/control.py --workload <cell> --seed <n> --seconds <s>

The control is the cell family's plain reference with one guarantee of
the configuration broken (for the register family: an `:info` op is
taken never to have happened).  It drives the same pool, window and
comparison as `run.py`, and has to come out not correct.  It needs no
chip, and the benchmark's own runs never run it.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    from benchmark.harness import cell as cell_mod, output, spec

    cell = spec.cell(spec.load(), args.workload)
    control = cell.family.Control(cell.config)
    out = cell_mod.run(cell, args.seed, args.seconds, False, T_START,
                       system=control)
    output.emit(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
