"""Seeded inputs and expected outputs of every job of one configuration.

The configuration names its plain reference (``"reference"``, loaded from
``references/<name>.py``; ``einsum`` where it names none), which draws a
job's input values, serves them by tag and computes the outputs they
should give.  Job ``k`` of a run with seed ``s`` draws from
``default_rng([s, k])``, so every seed gives the same sizes and the same
number of values, and a run can be checked after its window by drawing
each job's values again.  The program sees only these values, through the
input provider of the workload the harness registers.
"""

from __future__ import annotations

import numpy as np

from cell import reference


class Inputs:
    """Per-job input values of one configuration under one seed."""

    def __init__(self, config: dict, seed: int):
        self.config = config
        self.seed = int(seed)
        self.ref = reference(config)
        self.compare = self.ref.COMPARE     # "max_err" or "exact"

    def arrays(self, job: int) -> list[np.ndarray]:
        return self.ref.arrays(self.config, self.seed, job)

    def provider(self, job: int):
        """tag -> value, as the program's INPUT instructions ask."""
        return self.ref.provider(self.config, self.arrays(job))

    def expected(self, job: int) -> dict:
        """tag -> the value the job's output of that tag should be."""
        return self.ref.expected(self.config, self.arrays(job))

    def canonical(self, outputs: dict) -> dict:
        """The form in which two outputs are compared exactly."""
        form = getattr(self.ref, "canonical", None)
        return outputs if form is None else form(self.config, outputs)
