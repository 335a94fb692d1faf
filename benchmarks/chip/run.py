#!/usr/bin/env python3
"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload ycsb_load.build --seed 7 \\
        --seconds 30 --trace 0

Refuses to run (exit 2, no result) unless JAX finds a TPU with as many
chips as the cell asks for.  ``--trace 0`` prints the cell's end-to-end
metrics; ``--trace 1`` runs the same window under the JAX profiler and
prints the per-layer metrics, the device's busy seconds and a breakdown.
The last stdout line is the result JSON.  The compile cache is
``$JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache`` at the checkout's root.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args()
    from yardstick import harness
    try:
        result = harness.run(a.workload, a.seed, a.seconds, bool(a.trace),
                             t_start=T_START, root=ROOT)
    except harness.NoChip as exc:
        print(f"run.py: {exc}", file=sys.stderr)
        return 2
    harness.main_line(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
