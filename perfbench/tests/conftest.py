import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import json  # noqa: E402
import shutil  # noqa: E402

import pytest  # noqa: E402

GC_CONFIG = os.path.join(HERE, "data", "gc-merge-tiny.json")


def gc_root(path) -> str:
    """A checkout root whose BENCHMARK.json lists only the two-party merge
    configuration of ``data/``, under ``closed.b40``: the files a later
    change would add, and nothing of the benchmark edited."""
    os.makedirs(os.path.join(path, "perfbench", "configs"))
    shutil.copy(GC_CONFIG, os.path.join(path, "perfbench", "configs"))
    bench = {
        "configs": [{"name": "gc-merge-tiny",
                     "source": "MAGE (OSDI 2021) sec. 8.1.1 merge",
                     "file": "perfbench/configs/gc-merge-tiny.json",
                     "reduced": ["n", "prefetch_pages"],
                     "why": "two-party GC merge at a CPU's size"}],
        "workloads": [{"name": "merge.b40", "config": "gc-merge-tiny",
                       "traffic": "closed.b40", "chips": 1,
                       "why": "n=64 a party, budget 0.4"}],
        "per_layer": [{"name": "device.idle_share", "unit": "%",
                       "better": "lower", "source": "device_trace",
                       "layer": "device", "moves": "job_s"}]}
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return str(path)


@pytest.fixture
def gc_cell(tmp_path):
    """The two-party merge cell, loaded as ``run.py`` loads a cell; a
    test may switch its driver in ``config["job"]``."""
    import cell
    return cell.load("merge.b40", root=gc_root(tmp_path))
