"""Chip benchmark of the paired-end mapper: one run of one cell.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with a TPU.  The cell, its
configuration, traffic mix and per-layer metrics are found by name from
``BENCHMARK.json`` and the files under ``benchmarks/chip/``.  The last
line of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and ``checks`` last); the numbers compared for
``correct`` are also the last lines of standard error.  Without a TPU
the run exits non-zero and prints no result.

``--control 1`` runs the configuration's control (a cheaper path of the
program that breaks a stated guarantee) in the program's place; its run
must come out not correct.  The benchmark's own runs never pass it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path.cwd()
sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench.runner import run_cell

    run_cell(ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
             control=bool(args.control), t_start=T_START)


if __name__ == "__main__":
    main()
