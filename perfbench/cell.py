"""A cell of BENCHMARK.json, with every file it names found by name.

A cell names a configuration (``configs[].file``) and a traffic mix
(``traffic/<traffic>.json``).  The configuration's plain reference is
``references/<reference>.py`` (``einsum`` where it names none).  Its
per-layer metrics are the entries of ``per_layer`` whose ``workloads``
list it (or that list none), each read by ``metrics/<name>.py``.  Nothing
here knows any particular cell.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    per_layer: list[dict]

    def job_fields(self) -> dict:
        """The JobSpec fields of one job: the configuration's, then the
        mix's (plan mode and budget)."""
        return {**self.config["job"], **self.traffic["job"]}


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load(name: str, root: str = ROOT) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"perfbench: no cell {name!r}; cells: "
                         f"{sorted(cells)}")
    w = cells[name]
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
    with open(os.path.join(root, cfg["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def _plugin(kind: str, name: str):
    """The module ``<kind>/<name>.py`` of the benchmark."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
    return _plugin("metrics", metric).read


def reference(config: dict):
    """The plain reference a configuration names: ``arrays``,
    ``provider``, ``expected``, ``COMPARE`` and, where an exact comparison
    needs one, ``canonical``."""
    return _plugin("references", config.get("reference", "einsum"))
