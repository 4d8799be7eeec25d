#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this machine holds.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` at the root of the checkout; ``chipbench/harness.py``
finds each one's files by that name.  The last line of standard output
is one JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, and with ``--trace 1`` ``breakdown``).  With no TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no
result line.
"""
import time

T0 = time.time()          # set-up is timed from here

import sys                # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    # the checkout root, not this directory, heads the import path, so
    # no module here can shadow one of the standard library
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness
    return harness.main(argv, t0=T0)


if __name__ == "__main__":
    sys.exit(main())
