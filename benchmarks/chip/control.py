#!/usr/bin/env python3
"""Readings for the correctness limits, several seeds in one process.

    python3 benchmarks/chip/control.py --workload ycsb_load.build \\
        --seeds 11,12,13 --seconds 10 --mode control

``--mode program`` runs the cell as the benchmark does; ``--mode control``
runs it with the program's plain-f32 integral images switched on
(``yardstick/control.py``), where the comparison has to come out not
correct.  One JSON line per seed: the seed, ``correct`` and the numbers
compared.  The benchmark's own runs never run the control.  Refuses to run
without a TPU, like ``run.py``.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--mode", choices=("program", "control"), required=True)
    a = ap.parse_args()
    from yardstick import control, harness
    hooks = (control.plain_f32_images,) if a.mode == "control" else ()
    for seed in (int(s) for s in a.seeds.split(",")):
        try:
            res = harness.run(a.workload, seed, a.seconds, False,
                              t_start=time.monotonic(), root=ROOT,
                              hooks=hooks)
        except harness.NoChip as exc:
            print(f"control.py: {exc}", file=sys.stderr)
            return 2
        print(json.dumps({"seed": seed, "mode": a.mode,
                          "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
