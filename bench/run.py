"""The chip benchmark of the sparse-QAP placement service.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the accelerator this process
finds: builds the program's placement service (``src/repro``) over the
cell's machine, warms the shapes its requests reach, drives the closed
loop of the cell's traffic for ``--seconds``, checks every answer
against the float64 reference, and prints one JSON result line last.
``--trace 0`` reports the end-to-end metrics, ``--trace 1`` the
per-layer ones, read from program spans and a profiler trace.  Off the
TPU, or with fewer chips than the cell asks, it exits non-zero and
prints no result.  See ``harness.py``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402

import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


if __name__ == "__main__":
    sys.exit(harness.main(parse(), T_START))
