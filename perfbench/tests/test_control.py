"""The control: the plain reference, put in the program's place and
computed in float16, the nearest standard precision below the about 12
bits the configurations' CKKS parameters deliver (PERF.md), has to be
judged not correct, at each cell's own size, on every seed.  For the
two-party merge, which states no precision, the control breaks the order
it guarantees: the lists joined unmerged, or merged on 16 key bits.

    python -m pytest perfbench/tests/test_control.py
"""

import numpy as np
import pytest

import cell
import judge
import traffic

SEEDS = [2**31 + 101, 2**31 + 202, 2**31 + 303]
CELLS = ["nmatmul.b40", "rmvmul.b40", "nmatmul.resident"]


def control_checks(c: cell.Cell, seed: int, jobs: int = 2) -> dict:
    inputs = traffic.Inputs(c.config, seed)
    done = [{"index": k,
             "outputs": inputs.ref.outputs(c.config["output"],
                                           inputs.arrays(k), np.float16)}
            for k in range(jobs)]
    return judge.checks(c.config, c.traffic, done, inputs, [], [], 0)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name, seed):
    checks = control_checks(cell.load(name), seed)
    assert not judge.passed(checks)
    assert checks["max_err"]["value"] > checks["max_err"]["limit"]


@pytest.mark.parametrize("name", CELLS)
def test_reference_itself_is_correct(name):
    c = cell.load(name)
    inputs = traffic.Inputs(c.config, SEEDS[0])
    done = [{"index": 0, "outputs": inputs.expected(0)}]
    checks = judge.checks(c.config, c.traffic, done, inputs, [], [], 0)
    assert judge.passed(checks) and checks["max_err"]["value"] == 0.0


def gc_control_checks(c: cell.Cell, seed: int, merge, jobs: int = 2):
    inputs = traffic.Inputs(c.config, seed)
    out = c.config["output"]["tag_base"]
    chunk = c.config["chunk"]
    done = []
    for k in range(jobs):
        records = merge(inputs.arrays(k))
        done.append({"index": k, "outputs": {
            out + i: records[i * chunk:(i + 1) * chunk]
            for i in range(len(records) // chunk)}})
    return judge.checks(c.config, c.traffic, done, inputs, [], [], 0)


def _unmerged(arrays):
    return np.concatenate(arrays)


def _low_16_bits(arrays):
    both = np.concatenate(arrays)
    return both[np.argsort(both & np.uint64(0xFFFF), kind="stable")]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("merge", [_unmerged, _low_16_bits])
def test_gc_control_is_not_correct(gc_cell, merge, seed):
    """The merge reference in the program's place, broken: the parties'
    lists joined without merging, or merged on the key's low 16 bits."""
    checks = gc_control_checks(gc_cell, seed, merge)
    assert not judge.passed(checks)
    assert checks["mismatched"]["value"] > 0
    assert checks["missing"]["value"] == 0


def test_gc_reference_itself_is_correct(gc_cell):
    ref = traffic.Inputs(gc_cell.config, SEEDS[0]).ref
    checks = gc_control_checks(gc_cell, SEEDS[0],
                               lambda a: ref.merged(gc_cell.config, a))
    assert judge.passed(checks) and checks["mismatched"]["value"] == 0
