"""Workload harness: the ten §8.1 kernels + §8.8 applications.

Each workload packages (1) a DSL program parameterized by ProgramOptions,
(2) deterministic synthetic inputs, (3) a numpy oracle for its outputs, and
(4) protocol/page-size defaults matching the paper (GC: 64 KiB pages = 4096
wires; CKKS: word-addressed pages sized a few ciphertexts).

All workloads follow the paper's three-phase discipline (§8.1.3): inputs are
materialized in memory first, then the computation runs, then outputs are
written — deliberately NOT streaming, so that memory pressure is real.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np

from ..core.bytecode import Program
from ..core.workers import ProgramOptions, trace_workers

GC_PAGE_SHIFT = 12    # 4096 wires * 16 B = 64 KiB, the paper's GC page size
CKKS_PAGE_SHIFT = 14  # 16384 words = 128 KiB pages (scaled with our N)


@dataclasses.dataclass
class Workload:
    name: str
    protocol: str                      # 'gc' | 'ckks'
    build: Callable[[ProgramOptions], None]
    inputs: Callable[[int, int, int], Callable[[int], np.ndarray]]
    # (problem_size, worker, num_workers) -> provider(tag)
    oracle: Callable[[int], dict[int, np.ndarray]]
    page_shift: int = GC_PAGE_SHIFT
    default_n: int = 256
    params: dict = dataclasses.field(default_factory=dict)

    def trace(self, n: int | None = None, num_workers: int = 1,
              **extra) -> list[Program]:
        n = n or self.default_n
        extra = {**self.params, **extra}
        return trace_workers(self.build, protocol=self.protocol,
                             page_shift=self.page_shift_for(**extra),
                             num_workers=num_workers, problem_size=n,
                             extra=extra)

    def page_shift_for(self, ckks_params=None, **_) -> int:
        """Page size of a trace under ``extra``: a CKKS page grows until a
        top-level product ciphertext fits, since values never straddle
        pages; rings up to N=1024 keep the 2^14-slot page."""
        if ckks_params is None:
            return self.page_shift
        need = ckks_params.ct_slots(ckks_params.levels, ncomp=3)
        return max(self.page_shift, (need - 1).bit_length())


REGISTRY: dict[str, Workload] = {}


def register(w: Workload) -> Workload:
    REGISTRY[w.name] = w
    return w


def get(name: str) -> Workload:
    import repro.workloads.gc_workloads  # noqa: F401
    import repro.workloads.ckks_workloads  # noqa: F401
    import repro.workloads.apps  # noqa: F401
    import repro.workloads.agg_workload  # noqa: F401
    import repro.workloads.shamir_workloads  # noqa: F401
    return REGISTRY[name]


def all_names() -> list[str]:
    get("merge")  # force registration
    return sorted(REGISTRY)
