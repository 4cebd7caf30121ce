#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``workloads`` in BENCHMARK.json) names a configuration file under
chipbench/configs/ and a traffic mix under chipbench/traffic/.  The run
needs a TPU with the cell's chip count: without one it exits 3 and prints
no result.  Diagnostics go to standard error, ending with each number the
correctness check compared beside its limit; the last line of standard
output is one JSON object with ``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and
``check`` last.  With ``--trace 1`` the metrics are the cell's per-layer
metrics, read from a profiler trace of the window; otherwise its
end-to-end metrics.
"""
from __future__ import annotations

import time

T0 = time.monotonic()     # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="offer this many requests/s instead of the mix's "
                         "rate (for finding the knee; cells never set it)")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        import repro  # noqa: F401
        from chipbench import harness
        from chipbench.spec import SpecError, load_cell
        cell = load_cell(args.workload)
    except (ImportError, SpecError) as e:
        print(f"chipbench: cannot load the cell: {e}", file=sys.stderr)
        return 2
    try:
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                             T0, rate=args.rate)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
