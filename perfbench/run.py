"""Run one benchmark cell and print its result as the last stdout line.

    python3 perfbench/run.py --workload box32.p16.default --seed 7 \\
        --seconds 10 --trace 0

Run from the root of a checkout.  The cell, its configuration, its traffic
mix and its metrics are read from ``BENCHMARK.json`` and the files it
names.  Without an accelerator, with fewer chips than the cell asks for,
or in a checkout without the program under ``src/``, the run exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.getcwd()

# One BLAS thread, set before NumPy loads: the pipeline's host BLAS work
# is small, and a pool of one thread per core gains it nothing while its
# threads wait on any core that other load holds.
for _var in ("OPENBLAS_NUM_THREADS", "OMP_NUM_THREADS", "MKL_NUM_THREADS"):
    os.environ[_var] = "1"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"perfbench: no program under {src}", file=sys.stderr)
        return 2
    sys.path[:0] = [HERE, src]
    import pb_harness

    try:
        result = pb_harness.run_cell(ROOT, args.workload, args.seed,
                                     args.seconds, bool(args.trace),
                                     t_start=T_START)
    except pb_harness.SetupError as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
