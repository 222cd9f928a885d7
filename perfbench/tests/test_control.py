"""The control: the plain reference, put in the program's place and
computed in float16, the nearest standard precision below the about 12
bits the configurations' CKKS parameters deliver (PERF.md), has to be
judged not correct, at each cell's own size, on every seed.

    python -m pytest perfbench/tests/test_control.py
"""

import numpy as np
import pytest

import cell
import judge
import reference
import traffic

SEEDS = [2**31 + 101, 2**31 + 202, 2**31 + 303]
CELLS = ["nmatmul.b40", "rmvmul.b40", "nmatmul.resident"]


def control_checks(c: cell.Cell, seed: int, jobs: int = 2) -> dict:
    inputs = traffic.Inputs(c.config, seed)
    done = [{"index": k,
             "outputs": reference.outputs(c.config["output"],
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
    done = [{"index": 0, "outputs": reference.outputs(
        c.config["output"], inputs.arrays(0))}]
    checks = judge.checks(c.config, c.traffic, done, inputs, [], [], 0)
    assert judge.passed(checks) and checks["max_err"]["value"] == 0.0
