#!/usr/bin/env python3
"""One process, one cell, once: set up, warm up, measure, check, print one
JSON line.

    python3 benchmark/run.py --workload <config>.<mix> --seed <n> --seconds <s> --trace <0|1>

Knows no cell: ``harness/cells.py`` finds the configuration, the traffic mix
and the per-layer metrics by name, and the mix's ``kind`` picks the window.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    os.chdir(os.path.dirname(HERE))

    from harness import cells, common

    cell = cells.load_cell(a.workload)
    common.emit(cells.window_module(cell["kind"]).run(cell, a.seed, a.seconds, bool(a.trace), T_START))
    return 0


if __name__ == "__main__":
    sys.exit(main())
