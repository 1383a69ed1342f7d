#!/usr/bin/env python3
"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload mnist_mlp_k50.scan --seed 1 \\
        --seconds 10 --trace 0
    JAX_PLATFORMS=cpu python3 bench/run.py --workload mnist_mlp_k50.scan \\
        --seed 1 --seconds 2 --trace 1 --rehearse

A run builds the cell's deployment from its configuration (data, topology,
the program's model), compiles and warms the program through the entry
point a user calls, runs its first call from the seed (the one compared
with the plain reference), then measures whole calls for ``--seconds``.
``--trace 1`` records the window with the profiler and reports the cell's
per-layer metrics instead of its end-to-end ones.  The last line of
standard output is one JSON object; the numbers compared with the
reference, each beside its limit, are the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.  ``--rehearse`` runs the cell on the CPU
at the configuration's reduced ``rehearsal`` size (Pallas in interpret
mode) to find faults before a chip run, and prints no chip result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at the reduced size; no chip result")
    args = ap.parse_args()

    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    from benchlib import harness
    harness.use_checkout_cache()
    return harness.run(args.workload, args.seed, args.seconds,
                       bool(args.trace), args.rehearse, T_START)


if __name__ == "__main__":
    sys.exit(main())
