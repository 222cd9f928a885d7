"""The seeded generators, the plain references and the refusal to run
without a TPU.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests
"""

import dataclasses
import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import cell
import device
import traffic
from conftest import BENCH, GC_CONFIG

CONFIGS = ["ckks-nmatmul-n8", "ckks-rmvmul-n24"]
BIG_SEED = 2**31 + 2**30 + 12345
EINSUM = cell.reference({"reference": "einsum"})


def config(name: str) -> dict:
    return config_at(os.path.join(BENCH, "configs", name + ".json"))


def config_at(path: str) -> dict:
    with open(path) as f:
        return json.load(f)




def _digest(values) -> str:
    h = hashlib.sha256()
    for v in values:
        v = np.ascontiguousarray(v)
        h.update(f"{v.dtype.str}{v.shape}".encode())
        h.update(v.tobytes())
    return h.hexdigest()


def _draw(cfg: dict, seed: int, job: int) -> tuple[str, str]:
    """sha256 of the job's input arrays and of its reference outputs (tag
    by tag in tag order)."""
    inputs = traffic.Inputs(cfg, seed)
    arrays, want = inputs.arrays(job), inputs.expected(job)
    tags = sorted(want)
    return (_digest(arrays),
            _digest([np.asarray(tags)] + [want[t] for t in tags]))


# Recorded before inputs and references became plug-ins by name, so the
# CKKS cells draw and check exactly what they did then.
GOLDEN = {
    "ckks-nmatmul-n8/7/0": (
        "41f69795d341af6fca5eebd383a905a1b0cade580d41592d19516136b2c01792",
        "85070e6c9187a47a91070d2e13c168d31eae03e7f61fc503f951858f20979bd8"),
    "ckks-nmatmul-n8/7/1": (
        "d5c2120dd48e62b4691c777ffd9089806067f3c195d4558de47665ffe0a20842",
        "aca8406f903d013bc9ae5d3efa00c8c888736eba2b47b0882a884dd6bd2448a4"),
    "ckks-nmatmul-n8/2147495993/0": (
        "403afee0e3248a8a7173ed65d4d94cda541d1a5567f847386d41b9b90a4274bb",
        "cbfaa06829349dfd73c99dd8d8960e845fdf111415dc0456461f55b3d4f7b005"),
    "ckks-nmatmul-n8/2147495993/1": (
        "4a0ce4359a70a6eb77eae5904c0f06f9bd4f7736eef82ba944595648885ff96c",
        "5acf0972904a539deff791b9d2a5d801c6c0c10a65dbd1905836cd8a544f7318"),
    "ckks-nmatmul-n8/5368709125/0": (
        "35bed7ce690f5d6ca5a87379a8da996516362b8242fb24765d620e630627fd05",
        "51f0176ceb1dc36c5f8a6ff41b3013b942b09e1c7c50ca1be66b78244fe591fb"),
    "ckks-nmatmul-n8/5368709125/1": (
        "2d3029779476eb56e2e5fb11af158ec36886850b4b9a254ba91cdfc3aacefd8f",
        "47d4200e659a724c4b8bfc9db46d35be3c917ddb964e3ff770ce02f2e6e375d6"),
    "ckks-rmvmul-n24/7/0": (
        "24cbf06081840bd1eca2bb2c2c74a053fdd6cb29f3f7ed47688caf068f3e7dc8",
        "b7f4672995ee224f66e717d4ddb26164a00e0e6a56d1cd6a7f13394220d2ea91"),
    "ckks-rmvmul-n24/7/1": (
        "95336298909f1ded048d2bffbc5553c906ed41682d56153d53a27f004a7fdf85",
        "d53f486868656359847c96b00a307eeb29bd5a370dc34b13a6ee2dec78f4be48"),
    "ckks-rmvmul-n24/2147495993/0": (
        "3d603f335a06a6ee3e41fc8b0c6cc901d8545bcd275e6a7cd9482c2ad562526f",
        "62fc431aedc6ca6a9b3a01ed86d299351c3ecf3df357e3232ebbcf81ae40851d"),
    "ckks-rmvmul-n24/2147495993/1": (
        "5f0c09ba2c5a088a8c3187598829a12d5fd824528c2d67926164635f1f6a424a",
        "caf3b0e3a6d2463754b3c574dbb61201de3a403d396605354c66f1caf392283a"),
    "ckks-rmvmul-n24/5368709125/0": (
        "7a5af095ae61f1892379fc144238d9af1a7f203d832cdb52c8bdb8f77b225aa3",
        "45e328f87ad5c3819f6e9d0367220eceef90a34f4e031b4d4a48f745a0735b29"),
    "ckks-rmvmul-n24/5368709125/1": (
        "c66121e4ba32a7b07d6322476849441c1da756d7a8181569feb18a6de1bf0938",
        "463e4f23840201d395024659591973e41c18e3ba8ce33344a320e62b23d0f9f6"),
}


@pytest.mark.parametrize("key", sorted(GOLDEN))
def test_inputs_and_reference_are_unchanged(key):
    name, seed, job = key.split("/")
    assert _draw(config(name), int(seed), int(job)) == GOLDEN[key]


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
        shape = EINSUM.shape_of(spec["shape"], n)
        rows = [provide(spec["tag_base"] + i)
                for i in range(int(np.prod(shape)))]
        arrays.append(np.asarray(rows).reshape(shape + (slots,)))
    want = w.oracle(n, ckks_params=params)
    got = EINSUM.outputs(cfg["output"], arrays)
    assert sorted(got) == sorted(want)
    for tag in want:
        np.testing.assert_allclose(got[tag], want[tag], rtol=0, atol=1e-12)


def test_control_precision_is_coarser():
    cfg = config("ckks-nmatmul-n8")
    arrays = traffic.Inputs(cfg, 1).arrays(0)
    r64 = EINSUM.result(cfg["output"], arrays)
    r16 = EINSUM.result(cfg["output"], arrays, np.float16)
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


def test_merge_inputs_are_seeded_and_sorted():
    cfg = config_at(GC_CONFIG)
    a, b = traffic.Inputs(cfg, BIG_SEED), traffic.Inputs(cfg, BIG_SEED)
    assert a.compare == "exact"
    for x, y in zip(a.arrays(1), b.arrays(1)):
        assert x.dtype == np.uint64 and np.array_equal(x, y)
    other = traffic.Inputs(cfg, BIG_SEED + 1).arrays(1)
    for x, y, z in zip(a.arrays(1), a.arrays(0), other):
        assert x.shape == y.shape == z.shape == (cfg["job"]["n"],)
        assert not np.array_equal(x, y) and not np.array_equal(x, z)
        key = x & np.uint64(0xFFFFFFFF)
        assert np.all(np.diff(key.astype(np.int64)) >= 0)
        assert x.max() < 2**63


def test_merge_reference_matches_the_workload_oracle():
    """Fed the program's own records in its tag layout, the merge
    reference gives its oracle's outputs, compared canonically."""
    from repro.workloads import get
    cfg = config_at(GC_CONFIG)
    w = get("merge")
    n, chunk = cfg["job"]["n"], cfg["chunk"]
    provide = w.inputs(n, 0, 1)
    arrays = [np.concatenate([provide(s["tag_base"] + i)
                              for i in range(n // chunk)])
              for s in cfg["inputs"]]
    ref = traffic.Inputs(cfg, 0).ref
    got = ref.expected(cfg, arrays)
    want = w.oracle(n)
    assert sorted(got) == sorted(want)
    canon_got, canon_want = ref.canonical(cfg, got), ref.canonical(cfg, want)
    for tag in want:
        assert np.array_equal(canon_got[tag], canon_want[tag])
    serve = ref.provider(cfg, arrays)
    assert np.array_equal(serve(cfg["inputs"][1]["tag_base"] + 1),
                          provide(cfg["inputs"][1]["tag_base"] + 1))
    with pytest.raises(KeyError):
        serve(cfg["output"]["tag_base"])


def test_merge_canonical_form_reorders_only_within_equal_keys():
    cfg = config_at(GC_CONFIG)
    ref = traffic.Inputs(cfg, 0).ref
    rec = lambda key, pay: np.uint64(key | (pay << 32))       # noqa: E731
    base = {0: np.array([rec(1, 9), rec(5, 2)]),
            1: np.array([rec(5, 1), rec(7, 0)])}
    # the two records of key 5, across a chunk boundary, swapped
    swapped = {0: np.array([rec(1, 9), rec(5, 1)]),
               1: np.array([rec(5, 2), rec(7, 0)])}
    # keys out of order, every record still there
    unsorted = {0: np.array([rec(5, 2), rec(1, 9)]), 1: base[1]}
    same = lambda a, b: all(np.array_equal(x, y) for x, y in   # noqa: E731
                            zip(*(ref.canonical(cfg, o).values()
                                  for o in (a, b))))
    assert same(base, swapped)
    assert not same(base, unsorted)
    assert not same(base, {0: base[0], 1: np.array([rec(5, 3), rec(7, 0)])})
