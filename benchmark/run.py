#!/usr/bin/env python3
"""Runs one cell of the chip benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell, its configuration, traffic mix
and metrics are named in BENCHMARK.json and found by name under
benchmark/.  The run fails, printing no result, when JAX finds no TPU or
fewer chips than the cell asks for.  Set-up (imports, device init, the
seeded pool, warm-up) is timed from the top of this file; then a closed
loop checks the pool's histories for `--seconds`; then the reference
decides every history the window checked.  `--trace 0` reports the
cell's end-to-end metrics, `--trace 1` its per-layer metrics from program
spans, counters and a profiler trace.  The numbers that decide `correct`
come last on standard error and last in the result line, which is the
last line of standard output.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: JAX's persistent compilation cache, inside the checkout at a fixed
#: path; any directory the environment names is overridden.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def prepare_environment() -> None:
    """The program runs with its defaults: no JEPSEN_* setting (a
    checkerd address, a plan memo, a forced engine) reaches it."""
    for k in [k for k in os.environ if k.startswith("JEPSEN_")]:
        del os.environ[k]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR


def find_chips(need: int):
    """The devices, or None (with the reason on stderr) when they are
    not TPUs or too few."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"benchmark: JAX found no device: {e}", file=sys.stderr)
        return None
    if devs[0].platform != "tpu":
        print(f"benchmark: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        return None
    if len(devs) < need:
        print(f"benchmark: the cell needs {need} chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        return None
    return devs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    from benchmark.harness import cell as cell_mod, output, spec

    cell = spec.cell(spec.load(), args.workload)
    prepare_environment()
    try:
        from jepsen_tpu import compile_cache, core  # noqa: F401
    except ImportError as e:
        print(f"benchmark: the program is not here: {e}", file=sys.stderr)
        return 2
    marks = {"imports_s": time.monotonic() - T_START}
    if find_chips(cell.chips) is None:
        return 3
    import jax

    marks["device_init_s"] = time.monotonic() - T_START - marks["imports_s"]

    compile_cache.place()
    # Every program goes to the cache, however small or quick to build,
    # so that only a checkout's first run compiles.  No eviction, JAX's
    # own default, whatever JAX_COMPILATION_CACHE_MAX_SIZE says: with it
    # on, one entry without its `-atime` file fails every later write
    # (PERF.md, Open questions).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)

    out = cell_mod.run(cell, args.seed, args.seconds, bool(args.trace),
                       T_START, marks=marks)
    output.emit(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
