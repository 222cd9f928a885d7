"""Readings that a ``max_err`` limit is set from, in one process per cell.

    python3 perfbench/calibrate.py --workload nmatmul.b40 --seeds 12 [--first S]

For a configuration whose reference is ``einsum``.  Sets the cell up
once, then for each seed serves one job through the window's path and
reads the widest gap of its outputs against the float64 reference (the
program's reading), and the same gap of the float16 reference on that
job's inputs (the control's reading).  Prints one JSON line per seed and
a summary: the largest program reading, the smallest control reading,
and the limit in force.  Needs the chip, as a run does;
the benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first", type=int, default=2**31 + 5000)
    args = ap.parse_args(argv)

    import cell
    import device
    import harness
    import judge
    import numpy as np
    c = cell.load(args.workload)
    device.require(c.chips)
    rows = []
    with harness.Stand(c, args.first, trace=False) as stand:
        for seed in range(args.first, args.first + args.seeds):
            stand.inputs.seed = seed
            t = time.perf_counter()
            win = stand.drive(1e-3)             # one job
            program = stand.checks(win)
            job = win.jobs[0]
            want = stand.inputs.expected(job["index"])
            ctl = stand.inputs.ref.outputs(c.config["output"],
                                           stand.inputs.arrays(job["index"]),
                                           np.float16)
            control, _ = judge.max_gap(want, ctl)
            row = {"seed": seed, "program": program["max_err"]["value"],
                   "control": control, "correct": judge.passed(program),
                   "job_s": time.perf_counter() - t}
            rows.append(row)
            print(json.dumps(row), flush=True)
    print(json.dumps({
        "workload": c.name, "seeds": len(rows),
        "all_correct": all(r["correct"] for r in rows),
        "program_max": max(r["program"] for r in rows),
        "control_min": min(r["control"] for r in rows),
        "limit": c.config["limits"]["max_err"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
