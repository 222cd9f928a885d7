"""The seeded generator, the plain reference and the refusal to run
without a TPU.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests
"""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import cell
import device
import reference
import traffic
from conftest import BENCH

CONFIGS = ["ckks-nmatmul-n8", "ckks-rmvmul-n24"]
BIG_SEED = 2**31 + 2**30 + 12345


def config(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", CONFIGS)
def test_generator_is_seeded(name):
    cfg = config(name)
    a = traffic.Inputs(cfg, BIG_SEED)
    b = traffic.Inputs(cfg, BIG_SEED)
    for job in (0, 3):
        for x, y in zip(a.arrays(job), b.arrays(job)):
            assert np.array_equal(x, y)
    # every job and seed draws other values of the same shapes
    other = traffic.Inputs(cfg, BIG_SEED + 1).arrays(0)
    for x, y, z in zip(a.arrays(0), a.arrays(1), other):
        assert x.shape == y.shape == z.shape
        assert not np.array_equal(x, y) and not np.array_equal(x, z)
        assert x.min() >= -1.0 and x.max() < 1.0


@pytest.mark.parametrize("name", CONFIGS)
def test_provider_serves_every_input_tag(name):
    cfg = config(name)
    inp = traffic.Inputs(cfg, 5)
    arrays, provide = inp.arrays(2), inp.provider(2)
    for spec, arr in zip(cfg["inputs"], arrays):
        flat = arr.reshape(-1, cfg["slots"])
        assert np.array_equal(provide(spec["tag_base"]), flat[0])
        last = spec["tag_base"] + len(flat) - 1
        assert np.array_equal(provide(last), flat[-1])
    with pytest.raises(KeyError):
        provide(cfg["output"]["tag_base"])


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_matches_the_workload_oracle(name):
    """At a tiny ring (N=128), fed the workload's own data in the
    workload's own tag layout, the reference gives its oracle's values."""
    from repro.protocols.ckks.params import CkksParams
    from repro.workloads import get
    cfg = config(name)
    w = get(cfg["job"]["workload"])
    n = cfg["job"]["n"]
    params = CkksParams(n_ring=128, levels=cfg["job"]["ckks_levels"])
    slots = params.slots
    provide = w.inputs(n, 0, 1, ckks_params=params)
    arrays = []
    for spec in cfg["inputs"]:
        shape = traffic.shape_of(spec["shape"], n)
        rows = [provide(spec["tag_base"] + i)
                for i in range(int(np.prod(shape)))]
        arrays.append(np.asarray(rows).reshape(shape + (slots,)))
    want = w.oracle(n, ckks_params=params)
    got = reference.outputs(cfg["output"], arrays)
    assert sorted(got) == sorted(want)
    for tag in want:
        np.testing.assert_allclose(got[tag], want[tag], rtol=0, atol=1e-12)


def test_control_precision_is_coarser():
    cfg = config("ckks-nmatmul-n8")
    arrays = traffic.Inputs(cfg, 1).arrays(0)
    r64 = reference.result(cfg["output"], arrays)
    r16 = reference.result(cfg["output"], arrays, np.float16)
    np.testing.assert_allclose(
        r64, np.einsum(cfg["output"]["einsum"], *arrays), atol=1e-12)
    assert r16.dtype == np.float16 and np.abs(r16 - r64).max() > 1e-3


@dataclasses.dataclass
class FakeDevice:
    platform: str
    device_kind: str


def test_device_check_refuses_what_is_not_a_known_tpu():
    tpu = FakeDevice("tpu", "TPU v5 lite")
    stamp = device.check([tpu], 1)
    assert stamp["kind"] == "TPU v5 lite"
    assert stamp["peaks"]["hbm_bytes_per_s"] == 819e9
    with pytest.raises(device.NoChip, match="no TPU"):
        device.check([FakeDevice("cpu", "cpu")], 1)
    with pytest.raises(device.NoChip, match="not in"):
        device.check([FakeDevice("tpu", "TPU v99")], 1)
    with pytest.raises(device.NoChip, match="needs 4 chips"):
        device.check([tpu], 4)
    with pytest.raises(device.NoChip, match="no device"):
        device.check([], 1)


def _run(args, cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run([sys.executable, "perfbench/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_command_fails_without_a_tpu():
    root = os.path.dirname(BENCH)
    got = _run(["--workload", "nmatmul.b40", "--seed", str(BIG_SEED),
                "--seconds", "1", "--trace", "0"], root)
    assert got.returncode != 0
    assert "no TPU" in got.stderr
    assert "{" not in got.stdout


def test_command_fails_without_the_program(tmp_path):
    """A directory with BENCHMARK.json and the benchmark only."""
    import shutil
    root = os.path.dirname(BENCH)
    shutil.copy(os.path.join(root, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    got = _run(["--workload", "nmatmul.b40", "--seed", "3",
                "--seconds", "1", "--trace", "0"], tmp_path)
    assert got.returncode != 0
    assert "{" not in got.stdout


def test_every_cell_names_files_that_exist():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        c = cell.load(w["name"])
        assert c.config["name"] == w["config"]
        assert c.traffic["name"] == w["traffic"]
        for m in c.per_layer:
            assert callable(cell.reader(m["name"]))
