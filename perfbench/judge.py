"""Whether a run is correct: every job's outputs against the reference,
and the guarantees the configuration and the mix state.

Each number compared has a limit of its own; PERF.md gives the readings
each limit was set from.  The configuration's reference says how outputs
are compared (``COMPARE``):

* ``max_err``      the widest gap between a decrypted output slot and the
                   float64 reference over every job of the window (its
                   limit is the configuration's);
* ``mismatched``   where the comparison is exact: outputs that differ
                   from the reference's, each taken in the reference's
                   ``canonical`` form (limit 0).

The others are exact:

* ``missing``      outputs the reference has and a job did not return;
* ``failed``       jobs the daemon answered with an error;
* ``frames_over``  pages an engine held beyond the frame budget of the
                   mix (``frame_budget``): the larger of the budget share
                   of the working set and the configuration's floor;
* ``swap_bytes``   bytes an engine read or wrote through its swap tier
                   where the mix states every page resident (``no_swap``).
"""

from __future__ import annotations

import numpy as np


def frame_limit(config: dict, traffic: dict) -> int:
    share = float(traffic["job"]["memory_budget"])
    return max(int(share * int(config["working_set_pages"])),
               int(config["frame_floor_pages"]))


def max_gap(expected: dict, got: dict) -> tuple[float, int]:
    """(widest |got - expected| over every slot, outputs missing)."""
    worst, missing = 0.0, 0
    for tag, want in expected.items():
        have = got.get(tag)
        if have is None or np.shape(have) != np.shape(want):
            missing += 1
            continue
        gap = float(np.max(np.abs(np.asarray(have, np.float64) - want)))
        worst = gap if not gap <= worst else worst   # NaN stays NaN
    return worst, missing


def mismatches(expected: dict, got: dict) -> tuple[int, int]:
    """(outputs whose values differ, outputs missing), compared as exact
    integers."""
    differ, missing = 0, 0
    for tag, want in expected.items():
        have = got.get(tag)
        if have is None or np.shape(have) != np.shape(want):
            missing += 1
        elif not np.array_equal(np.asarray(have).astype(object),
                                np.asarray(want).astype(object)):
            differ += 1
    return differ, missing


def checks(config: dict, traffic: dict, jobs: list[dict], inputs,
           engines: list[dict], executes: list[dict], failed: int) -> dict:
    """{name: {"value", "limit"}} over the window's jobs; ``jobs[i]`` has
    ``index`` (its input draw) and ``outputs`` (tag -> values)."""
    exact = inputs.compare == "exact"
    worst, differ, missing = 0.0, 0, 0
    for job in jobs:
        want = inputs.expected(job["index"])
        if exact:
            bad, miss = mismatches(inputs.canonical(want),
                                   inputs.canonical(job["outputs"]))
            differ += bad
        else:
            gap, miss = max_gap(want, job["outputs"])
            worst = gap if not gap <= worst else worst
        missing += miss
    if exact:
        out = {"mismatched": {"value": differ, "limit": 0}}
    else:
        out = {"max_err": {"value": worst,
                           "limit": float(config["limits"]["max_err"])}}
    out.update({"missing": {"value": missing, "limit": 0},
                "failed": {"value": failed, "limit": 0}})
    guarantee = traffic["guarantee"]
    if guarantee == "frame_budget":
        held = max((e["pages"] + e["prefetch_pages"] for e in engines),
                   default=0)
        out["frames_over"] = {
            "value": max(held - frame_limit(config, traffic), 0),
            "limit": 0}
    elif guarantee == "no_swap":
        out["swap_bytes"] = {
            "value": sum(s.io_read_bytes + s.io_write_bytes
                         for e in executes for s in e["stats"]),
            "limit": 0}
    else:
        raise ValueError(f"unknown guarantee {guarantee!r}")
    return out


def passed(result: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in result.values())
