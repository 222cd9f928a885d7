"""Run one cell of BENCHMARK.json once and print its result line.

    python3 perfbench/run.py --workload nmatmul.b40 --seed 7 --seconds 51 --trace 0

From the root of a checkout, on a machine that holds the chips the cell
asks for.  With ``--trace 0`` the result's metrics are the cell's
end-to-end ones, with ``--trace 1`` its per-layer ones, read from a
profiler trace of the window and the benchmark's host spans.  The last
line of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``: every number compared beside its limit, which also
end standard error).  Without a TPU, with fewer chips than the cell asks
for, or with a device kind that ``peaks.json`` lacks, it prints no result
and exits non-zero.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import cell
    import device
    import harness
    import repro  # noqa: F401  (the system under test must be here)
    c = cell.load(args.workload)
    dev = device.require(c.chips)
    harness.log(f"chip up at {time.perf_counter() - T0:.3f} s: {dev['kind']}")
    result = harness.run(c, args.seed, args.seconds, bool(args.trace),
                         T0, dev)
    for name, chk in result["checks"].items():
        print(f"check {name}: {chk['value']!r} (limit {chk['limit']!r})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
