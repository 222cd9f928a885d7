"""The one traffic generator: seeded input values for every job.

A configuration file names its inputs by tag base and shape (``"n"`` in a
shape is the job's problem size); every value is a vector of ``slots``
reals drawn uniformly from [-1, 1).  Job ``k`` of a run with seed ``s``
draws from ``default_rng([s, k])``, so every seed gives the same sizes and
the same number of values, and a run can be checked after its window by
drawing each job's values again.  The program sees only these values,
through the input provider of the workload the harness registers.
"""

from __future__ import annotations

import numpy as np


def shape_of(spec: list, n: int) -> tuple[int, ...]:
    return tuple(n if d == "n" else int(d) for d in spec)


class Inputs:
    """Per-job input values of one configuration under one seed."""

    def __init__(self, config: dict, seed: int):
        self.n = int(config["job"]["n"])
        self.slots = int(config["slots"])
        self.specs = config["inputs"]
        self.seed = int(seed)

    def arrays(self, job: int) -> list[np.ndarray]:
        """One array per input, shaped (*shape, slots), float64."""
        rng = np.random.default_rng([self.seed, job])
        return [rng.uniform(-1.0, 1.0,
                            shape_of(s["shape"], self.n) + (self.slots,))
                for s in self.specs]

    def provider(self, job: int):
        """tag -> slot vector, as the program's INPUT instructions ask."""
        flat = [(int(s["tag_base"]), a.reshape(-1, self.slots))
                for s, a in zip(self.specs, self.arrays(job))]

        def value(tag: int) -> np.ndarray:
            for base, rows in flat:
                if base <= tag < base + len(rows):
                    return rows[tag - base]
            raise KeyError(f"no input with tag {tag}")
        return value
