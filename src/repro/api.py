"""The deployment facade: a declarative ``JobSpec`` plus a staged ``Session``.

The paper's deployment unit is a config file plus two stages — ``mage plan``
produces on-disk memory programs, the engine executes them (§6, §8.1.3).
This module is that unit for the repro: a frozen :class:`JobSpec` names a
workload, a memory budget, a plan mode and a driver/storage pair, and a
:class:`Session` runs the staged pipeline

    trace() → plan() → execute(real=…) / simulate(cost_fn)

on top of the single worker-orchestration core in ``core.workers``.  Plans
can be saved to a directory (``save_plan``) and executed later or elsewhere
(``Session.from_plan`` / ``python -m repro run``); every planned program
carries the spec hash in its ``meta`` so stale or tampered artifacts are
rejected instead of silently executed.

Drivers, storage backends and transports are *registries* keyed by name
(``{"gc-plaintext", "gc-2party", "ckks"} × {"ram", "memmap"} ×
{"inproc", "tcp", "shaped"}`` in-tree), so call sites select protocols by
string instead of importing concrete classes; ``register_driver`` /
``register_storage`` / ``register_transport`` extend them (§4.3's
extensibility argument, surfaced at the API).

All communication — intra-party NET_* directives and inter-party garbled
traffic — rides one transport fabric (``core.transport``).  A spec's
``transport`` picks the backend and its ``fabric`` (:class:`FabricSpec`)
places endpoints: ``rank=None`` runs every engine in this process
(threads), ``rank=k`` runs exactly one engine against remote peers —
that is ``python -m repro run --worker k --peers ...`` (§5.2's
one-engine-per-worker-per-party deployment; see docs/DISTRIBUTED.md).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import shutil
import tempfile
from typing import Callable

import numpy as np

from .core.bytecode import (Program, ProgramFile, write_program)
from .core.engine import EngineStats, ProtocolDriver
from .core.liveness import working_set_pages_stream
from .core.replacement import CORES
from .core.planner import PlanConfig, PlanReport
from .core.simulator import (DeviceModel, SimResult, simulate_memory_program,
                             simulate_os_paging, simulate_unbounded)
from .core.storage import MemmapStorage, RamStorage, StorageBackend
from .core.transport import Fabric, FabricSpec, LinkStats, build_fabric
from .core.transport import register_transport  # noqa: F401  (re-export)
from .core.workers import EngineJob, plan_workers, run_engines
from .protocols.ckks import CkksDriver, CkksParams
from .protocols.garbled.driver import (EvaluatorDriver, GarblerDriver,
                                       PlaintextDriver)
from .protocols.garbled.gates import PartyChannel
from .protocols.shamir.driver import ShamirDriver
from .workloads import Workload, get

PLAN_MODES = ("memory", "streaming", "unbounded")
#: Engine execution backends: "scalar" is the per-instruction reference
#: loop; "batched" precomputes a batch schedule from the plan's oblivious
#: instruction stream and executes uniform independent groups through
#: ``driver.execute_batch`` (see repro.exec and docs/ENGINE.md).
#: "overlap" additionally precomputes an out-of-order issue schedule that
#: hoists NET_SENDs, defers NET_RECV completions and fills the WAN
#: latency gap with independent local work (see repro.exec.overlap and
#: docs/OVERLAP.md).  Like plan_core/sim_core, all three are
#: output-identical by construction.
EXEC_BACKENDS = ("scalar", "batched", "overlap")

#: Version stamped into every machine-readable output (CLI ``--json``
#: files and the serving daemon's protocol responses) so consumers can
#: evolve with the formats.
SCHEMA_VERSION = 1

#: bytes per address-space slot, per protocol — a GC slot is one 128-bit
#: wire label, a CKKS or Shamir slot one 8-byte word (what the timing
#: simulator and the OS-paging baseline charge per page).
SLOT_BYTES = {"gc": 16, "ckks": 8, "shamir": 8}

#: JobSpec fields that determine the planned memory program.  Execution
#: details (driver, exec_backend, storage, workdir, parallelism, chunking)
#: are excluded:
#: a plan produced under any of them is valid under all of them, and
#: ``plan_mode`` / ``plan_core`` / ``sim_core`` are excluded because the
#: streaming and in-memory pipelines, the array and scalar planner cores,
#: and the array and scalar simulator cores are all output-identical by
#: construction (tested).
PLAN_HASH_FIELDS = ("workload", "n", "num_workers", "memory_budget",
                    "lookahead", "prefetch_pages", "policy", "swap_bypass",
                    "ckks_ring", "ckks_levels")

#: The subset of PLAN_HASH_FIELDS that determines the *traced* bytecode:
#: the DSL trace is a pure function of the workload shape, so traced
#: programs (and their next-use sidecars) are shared across every budget
#: / lookahead / policy variation of the same shape in the artifact
#: cache (``JobSpec.trace_hash``).
TRACE_HASH_FIELDS = ("workload", "n", "num_workers", "ckks_ring",
                     "ckks_levels")

JOB_FILE = "job.json"


class SpecMismatchError(ValueError):
    """A plan artifact does not match the spec that claims it."""


# ---------------------------------------------------------------------------
# driver / storage registries
# ---------------------------------------------------------------------------

# A driver factory builds ProtocolDrivers for the endpoints THIS process
# hosts: it gets the session and the connected Fabric and returns
# {global_rank: driver} for fabric.hosted only — so a distributed
# single-rank process constructs exactly its own driver.  Global rank =
# party * num_workers + worker; the registry records how many parties a
# driver deploys (gc-2party: 2, everything else: 1).  Outputs are
# collected from every hosted driver exposing a non-empty ``.outputs``
# (for two-party GC that is the evaluator side only, matching the
# protocol).

DriverFactory = Callable[["Session", Fabric], dict[int, ProtocolDriver]]
StorageFactory = Callable[[tuple, np.dtype], StorageBackend]


@dataclasses.dataclass(frozen=True)
class DriverDef:
    factory: DriverFactory
    parties: int = 1


DRIVERS: dict[str, DriverDef] = {}
STORAGE_BACKENDS: dict[str, StorageFactory] = {}


def register_driver(name: str, factory: DriverFactory,
                    parties: int = 1) -> None:
    DRIVERS[name] = DriverDef(factory, parties)


def driver_parties(name: str) -> int:
    """Number of parties (rank blocks) a registered driver deploys."""
    return _driver_def(name).parties


def _driver_def(name: str) -> DriverDef:
    try:
        return DRIVERS[name]
    except KeyError:
        raise KeyError(f"unknown driver {name!r}; registered: "
                       f"{sorted(DRIVERS)}") from None


def register_storage(name: str, factory: StorageFactory) -> None:
    STORAGE_BACKENDS[name] = factory


def _gc_plaintext_drivers(s: "Session", fx: Fabric
                          ) -> dict[int, ProtocolDriver]:
    w, n, p = s.workload, s.spec.n, s.spec.num_workers
    return {r: PlaintextDriver(w.inputs(n, r % p, p)) for r in fx.hosted}


def _gc_two_party_drivers(s: "Session", fx: Fabric
                          ) -> dict[int, ProtocolDriver]:
    # one cross-party link per worker pair: garbler rank wk sends to
    # evaluator rank p + wk (the one-to-one inter-party topology of Fig. 3)
    w, n, p = s.workload, s.spec.n, s.spec.num_workers
    out: dict[int, ProtocolDriver] = {}
    for r in fx.hosted:
        party, wk = divmod(r, p)
        link = PartyChannel(fx.transport_for(r), src=wk, dst=p + wk)
        if party == 0:
            out[r] = GarblerDriver(link, w.inputs(n, wk, p), seed=7)
        else:
            out[r] = EvaluatorDriver(link, w.inputs(n, wk, p))
    return out


def _ckks_drivers(s: "Session", fx: Fabric) -> dict[int, ProtocolDriver]:
    w, n, p = s.workload, s.spec.n, s.spec.num_workers
    params = s.ckks_params()
    return {r: CkksDriver(params, w.inputs(n, r % p, p, ckks_params=params),
                          seed=0xCEC5)
            for r in fx.hosted}


def _shamir_drivers(s: "Session", fx: Fabric) -> dict[int, ProtocolDriver]:
    # the n Shamir parties ARE the n workers of one registry party: worker
    # rank == party index, MUL resharing rounds ride the all-to-all worker
    # links as ordinary NET_* directives (see docs/SHAMIR.md)
    w, n, p = s.workload, s.spec.n, s.spec.num_workers
    return {r: ShamirDriver(p, r % p, w.inputs(n, r % p, p))
            for r in fx.hosted}


def _shamir_fixed(n_parties: int) -> DriverFactory:
    def factory(s: "Session", fx: Fabric) -> dict[int, ProtocolDriver]:
        if s.spec.num_workers != n_parties:
            raise ValueError(
                f"driver shamir-{n_parties}party needs num_workers="
                f"{n_parties}, got {s.spec.num_workers}")
        return _shamir_drivers(s, fx)
    return factory


register_driver("gc-plaintext", _gc_plaintext_drivers)
register_driver("gc-2party", _gc_two_party_drivers, parties=2)
register_driver("ckks", _ckks_drivers)
register_driver("shamir", _shamir_drivers)
register_driver("shamir-3party", _shamir_fixed(3))
register_driver("shamir-5party", _shamir_fixed(5))
register_storage("ram", lambda shape, dtype: RamStorage(shape, dtype))
register_storage("memmap", lambda shape, dtype: MemmapStorage(shape, dtype))


# ---------------------------------------------------------------------------
# discovery: the stable way to enumerate what the registries offer
# ---------------------------------------------------------------------------


def list_workloads() -> list[str]:
    """Registered workload names (`JobSpec.workload` values)."""
    from .workloads import all_names
    return all_names()


def list_drivers() -> list[str]:
    """Registered protocol drivers (`JobSpec.driver` values)."""
    return sorted(DRIVERS)


def list_storages() -> list[str]:
    """Registered storage backends (`JobSpec.storage` values)."""
    return sorted(STORAGE_BACKENDS)


def list_transports() -> list[str]:
    """Registered transport fabrics (`JobSpec.transport` values)."""
    from .core.transport import TRANSPORTS
    return sorted(TRANSPORTS)


# ---------------------------------------------------------------------------
# JobSpec
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class JobSpec:
    """Declarative description of one trace→plan→execute job.

    ``memory_budget`` is the paper's T: an ``int`` is an absolute frame
    count used as-is; a ``float`` in (0, 1] is a fraction of the worker's
    working set, resolved per worker with the benchmark harness's clamping
    (floor of ``8 + prefetch_pages`` frames, capped below the working set
    so there is real memory pressure, prefetch buffer at most a quarter of
    the budget).  ``None`` requires ``plan_mode="unbounded"``.

    ``transport`` + ``fabric`` are execution details (never part of the
    plan hash): the transport registry name and the endpoint placement /
    link shaping (:class:`~repro.core.transport.FabricSpec`).
    """
    workload: str
    n: int | None = None                  # problem size (None → default_n)
    num_workers: int = 1
    memory_budget: int | float | None = None
    lookahead: int = 10_000               # plan knobs (paper l, B, policy)
    prefetch_pages: int = 0
    policy: str = "min"
    swap_bypass: bool = False
    plan_mode: str = "memory"             # memory | streaming | unbounded
    plan_core: str = "array"              # array | scalar (identical output)
    sim_core: str = "array"               # simulator core (identical results)
    parallel_plan: bool | str = "serial"  # serial | thread | process
    driver: str = "auto"                  # auto → protocol default
    exec_backend: str = "scalar"          # scalar | batched (see docs/ENGINE.md)
    storage: str = "ram"                  # ram | memmap
    transport: str = "inproc"             # inproc | tcp | shaped
    fabric: FabricSpec | None = None      # endpoint placement / shaping
    workdir: str | None = None            # streaming plan files live here
    chunk_instrs: int = 8192
    track_plan_memory: bool = False
    ckks_ring: int | None = None          # CKKS N override (benchmarks)
    ckks_levels: int | None = None

    def __post_init__(self):
        if self.plan_mode not in PLAN_MODES:
            raise ValueError(f"plan_mode must be one of {PLAN_MODES}, "
                             f"got {self.plan_mode!r}")
        if self.plan_core not in CORES:
            raise ValueError(f"plan_core must be one of {CORES}, "
                             f"got {self.plan_core!r}")
        if self.sim_core not in CORES:
            raise ValueError(f"sim_core must be one of {CORES}, "
                             f"got {self.sim_core!r}")
        if self.exec_backend not in EXEC_BACKENDS:
            raise ValueError(f"exec_backend must be one of {EXEC_BACKENDS}, "
                             f"got {self.exec_backend!r}")
        if self.plan_mode == "unbounded":
            if self.memory_budget is not None:
                raise ValueError("unbounded jobs take no memory_budget")
        elif self.memory_budget is None:
            raise ValueError(f"plan_mode={self.plan_mode!r} needs a "
                             f"memory_budget (frames or working-set fraction)")
        if isinstance(self.memory_budget, float) and \
                not 0.0 < self.memory_budget <= 1.0:
            raise ValueError("fractional memory_budget must be in (0, 1]")
        if isinstance(self.fabric, dict):  # from_dict / JSON round-trip
            object.__setattr__(self, "fabric", FabricSpec(**self.fabric))

    # -- derived / resolved ---------------------------------------------------

    def normalized(self, workload: "Workload | None" = None) -> "JobSpec":
        """Fill workload-dependent defaults (n, driver) in."""
        w = workload if workload is not None else get(self.workload)
        changes = {}
        if self.n is None:
            changes["n"] = w.default_n
        if self.driver == "auto":
            changes["driver"] = {"ckks": "ckks", "shamir": "shamir"}.get(
                w.protocol, "gc-plaintext")
        return dataclasses.replace(self, **changes) if changes else self

    def plan_hash(self, workload: "Workload | None" = None) -> str:
        """Digest of the plan-determining fields (see PLAN_HASH_FIELDS)."""
        return self._hash(PLAN_HASH_FIELDS, workload)

    def trace_hash(self, workload: "Workload | None" = None) -> str:
        """Digest of the trace-determining fields (see TRACE_HASH_FIELDS)."""
        return self._hash(TRACE_HASH_FIELDS, workload)

    def _hash(self, fields: tuple[str, ...],
              workload: "Workload | None" = None) -> str:
        spec = self.normalized(workload)
        payload = {k: getattr(spec, k) for k in fields}
        blob = json.dumps(payload, sort_keys=True, separators=(",", ":"))
        return hashlib.sha256(blob.encode()).hexdigest()[:16]

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "JobSpec":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown JobSpec fields: {sorted(unknown)}")
        return cls(**d)


def resolve_plan_config(spec: JobSpec, prog: Program,
                        working_set: int | None = None) -> PlanConfig:
    """Turn a spec's budget into a concrete per-worker PlanConfig."""
    b = spec.memory_budget
    prefetch = spec.prefetch_pages
    if isinstance(b, float):
        ws = working_set if working_set is not None \
            else working_set_pages_stream(prog)
        min_frames = 8 + prefetch
        budget = max(int(ws * b), min_frames)
        budget = min(budget, max(ws - 1, min_frames))
        prefetch = min(prefetch, max(budget // 4, 1))
    else:
        budget = int(b)
    return PlanConfig(num_frames=budget, lookahead=spec.lookahead,
                      prefetch_pages=prefetch, policy=spec.policy,
                      swap_bypass=spec.swap_bypass, core=spec.plan_core)


# ---------------------------------------------------------------------------
# simulate() result
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class WorkerScenarios:
    """Per-worker §8.2 scenario timings + plan metadata."""
    unbounded: SimResult
    os: SimResult
    mage: SimResult
    report: PlanReport
    config: PlanConfig
    working_set_pages: int
    page_bytes: int
    instructions: int
    program_bytes: int                   # memory program size (file or est.)


# ---------------------------------------------------------------------------
# Session
# ---------------------------------------------------------------------------


#: plan shapes whose device work ``Session.prepare`` has compiled
_PREPARED: set[tuple[str, str, str]] = set()


class Session:
    """Staged trace→plan→execute/simulate over one JobSpec.

    Stages cache: ``trace()`` and ``plan()`` are idempotent, ``execute()``
    and ``simulate()`` call them as needed.  Streaming plans with no
    explicit ``workdir`` live in a session-owned temp directory — use the
    session as a context manager (or call :meth:`close`) to clean it up,
    or :meth:`save_plan` to move the artifacts somewhere durable.
    """

    def __init__(self, spec: JobSpec, workload: Workload | None = None,
                 cache=None):
        """``workload`` overrides the registry lookup (e.g. an unregistered
        or parameter-adjusted Workload object); its name must match.

        ``cache`` — an :class:`~repro.serve_daemon.ArtifactCache` or a
        cache-root path — makes ``trace()`` and ``plan()`` serve repeated
        job shapes from validated on-disk artifacts (see docs/SERVE.md).
        Custom workload objects bypass the cache: their traced programs
        are not a pure function of the registry name."""
        if workload is not None and workload.name != spec.workload:
            raise ValueError(f"workload object {workload.name!r} does not "
                             f"match spec.workload {spec.workload!r}")
        self.workload: Workload = workload if workload is not None \
            else get(spec.workload)
        self.spec = spec.normalized(self.workload)
        self._progs: list[Program | ProgramFile] | None = None
        self._planned: list[Program | ProgramFile] | None = None
        self._cfgs: list[PlanConfig | None] | None = None
        self._ws: dict[int, int] = {}
        self._tmpdir: str | None = None
        self._cache = None
        self._plan_probed = False
        self._trace_anns: list[str] | None = None
        #: per-stage cache outcomes of THIS session: {"trace"|"plan":
        #: "hit"|"miss"}; stages that never consulted the cache are absent
        self.cache_events: dict[str, str] = {}
        if cache is not None:
            self.set_cache(cache)
        self.plan_reports: list[PlanReport] = []
        self.engine_stats: list[EngineStats] = []
        #: sent-traffic accounting of the last execute()'s fabric,
        #: (src_rank, dst_rank, tag) -> LinkStats
        self.transport_stats: dict[tuple[int, int, int], LinkStats] = {}

    def set_cache(self, cache) -> None:
        """Attach an artifact cache (an ArtifactCache or a root path)."""
        from .serve_daemon.cache import ArtifactCache
        if not isinstance(cache, ArtifactCache):
            cache = ArtifactCache(cache)
        self._cache = cache

    @property
    def cache(self):
        """The attached ArtifactCache, or None."""
        return self._cache

    def _usable_cache(self):
        """Custom (non-registry) workload objects must bypass the cache."""
        if self._cache is None:
            return None
        try:
            registered = get(self.spec.workload)
        except KeyError:
            return None
        return self._cache if self.workload is registered else None

    # -- lifecycle -------------------------------------------------------------

    def close(self) -> None:
        if self._tmpdir is not None:
            shutil.rmtree(self._tmpdir, ignore_errors=True)
            self._tmpdir = None

    def __enter__(self) -> "Session":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- helpers ---------------------------------------------------------------

    @property
    def protocol(self) -> str:
        return self.workload.protocol

    def ckks_params(self) -> CkksParams:
        from .workloads.ckks_workloads import PARAMS as DEFAULT_CKKS
        base = self.workload.params.get("ckks_params", DEFAULT_CKKS)
        if self.spec.ckks_ring is None and self.spec.ckks_levels is None:
            return base
        # replace, don't rebuild: keep the base's scale/noise parameters
        return dataclasses.replace(
            base, n_ring=self.spec.ckks_ring or base.n_ring,
            levels=self.spec.ckks_levels or base.levels)

    def workload_extra(self) -> dict:
        """What the workload's trace, inputs and oracle are built from
        beyond the problem size: the CKKS parameters, for CKKS."""
        if self.protocol == "ckks":
            return {"ckks_params": self.ckks_params()}
        return {}

    def check(self, outputs: dict[int, np.ndarray]) -> None:
        """Compare outputs against the workload's oracle for this spec."""
        check_outputs(self.workload, self.spec.n, outputs,
                      **self.workload_extra())

    def working_set(self, worker: int = 0) -> int:
        """Peak live pages of one worker's virtual trace (w of §2.4.3)."""
        if worker not in self._ws:
            prog = self.trace()[worker]
            self._ws[worker] = working_set_pages_stream(prog)
        return self._ws[worker]

    def _workdir(self) -> str | None:
        if self.spec.workdir is not None:
            return self.spec.workdir
        if self.spec.plan_mode == "streaming":
            if self._tmpdir is None:
                self._tmpdir = tempfile.mkdtemp(prefix="mage_job_")
            return self._tmpdir
        return None

    # -- stage 1: trace --------------------------------------------------------

    def trace(self, cache_dir=None) -> list[Program | ProgramFile]:
        """Trace the workload's DSL program, one bytecode per worker; the
        spec hash is stamped into every program's meta (placement, §6.1).

        With a cache attached (``cache_dir=`` here, or ``cache=`` at
        construction), a repeated trace shape (``spec.trace_hash()``) is
        served as validated FREE-stripped bytecode files + next-use
        sidecars instead of re-running the DSL — the slowest §8.2 stage.
        A fresh trace populates the cache, and the session adopts the
        cached files so cold and hot runs plan identically."""
        if cache_dir is not None:
            self.set_cache(cache_dir)
        if self._progs is None:
            spec = self.spec
            cache = self._usable_cache()
            if cache is not None:
                got = cache.get_trace(spec, self.workload)
                if got is not None:
                    self.cache_events["trace"] = "hit"
                    self._adopt_trace(*got)
                    return self._progs
                self.cache_events["trace"] = "miss"
            progs = self.workload.trace(spec.n, spec.num_workers,
                                        **self.workload_extra())
            if cache is not None:
                self._adopt_trace(*cache.put_trace(
                    spec, self.workload, progs,
                    chunk_instrs=spec.chunk_instrs))
            else:
                h = spec.plan_hash(self.workload)
                for p in progs:
                    p.meta["spec_hash"] = h
                    p.meta["job_spec"] = spec.to_dict()
                self._progs = progs
        return self._progs

    def _adopt_trace(self, progs: list[ProgramFile],
                     anns: list[str]) -> None:
        """Use cache-resident bytecode files as this session's trace; the
        spec stamp lives in the entry as a pure trace hash, so the
        session's own spec identity is restamped in-memory."""
        h = self.spec.plan_hash(self.workload)
        for pf in progs:
            pf.meta["spec_hash"] = h
            pf.meta["job_spec"] = self.spec.to_dict()
        self._progs = list(progs)
        self._trace_anns = list(anns)

    # -- stage 2: plan ---------------------------------------------------------

    def plan(self, cache_dir=None) -> list[Program | ProgramFile]:
        """Replacement + scheduling per worker (§6.1) under the spec's
        budget and mode; returns memory programs (files when streaming).

        With a cache attached, a repeated plan shape (``spec.plan_hash()``)
        is served from validated memory-program files — zero tracing and
        zero planning — with the resolved per-worker configs and reports
        restored, so a cache-hit session can still ``simulate()``."""
        if cache_dir is not None:
            self.set_cache(cache_dir)
        if self._planned is None:
            spec = self.spec
            if spec.plan_mode != "unbounded" and self.plan_if_cached():
                return self._planned
            progs = self.trace()
            if spec.plan_mode == "unbounded":
                self._planned = list(progs)
                self._cfgs = [None] * len(progs)
                self.plan_reports = [PlanReport() for _ in progs]
            else:
                streaming = spec.plan_mode == "streaming"
                cfgs = [resolve_plan_config(spec, p, self.working_set(i))
                        if isinstance(spec.memory_budget, float)
                        else resolve_plan_config(spec, p)
                        for i, p in enumerate(progs)]
                if not streaming:
                    # the in-memory planner cores need .instrs; cache-hit
                    # traces are files, so materialize them (small by
                    # definition of the in-memory mode)
                    progs = [p.read_program() if isinstance(p, ProgramFile)
                             else p for p in progs]
                planned, reports = plan_workers(
                    progs, cfgs, parallel=spec.parallel_plan,
                    streaming=streaming,
                    workdir=self._workdir(),
                    track_memory=spec.track_plan_memory,
                    chunk_instrs=spec.chunk_instrs,
                    annotations=self._trace_anns if streaming else None)
                self._planned = planned
                self._cfgs = cfgs
                self.plan_reports = reports
                cache = self._usable_cache()
                if cache is not None:
                    cache.put_plan(spec, self.workload, planned, cfgs,
                                   reports)
        return self._planned

    def plan_if_cached(self) -> bool:
        """Probe the artifact cache for this spec's plan; on a hit, load
        the memory programs + resolved configs + reports and return True
        (the daemon uses this to size admission without planning)."""
        if self._planned is not None:
            return True
        cache = self._usable_cache()
        if cache is None or self.spec.plan_mode == "unbounded" or \
                self._plan_probed:   # one probe per session: don't double-
            return False             # count misses when plan() re-enters
        self._plan_probed = True
        got = cache.get_plan(self.spec, self.workload)
        if got is None:
            self.cache_events["plan"] = "miss"
            return False
        self.cache_events["plan"] = "hit"
        planned, cfgs, reports = got
        self._planned = list(planned)
        self._cfgs = list(cfgs)
        self.plan_reports = list(reports)
        return True

    def prepare(self) -> None:
        """Compile ahead the device work that executing the plan will
        launch, so that ``execute`` compiles nothing: the batched CKKS
        multiply chain at each group size the batch schedules hold
        (``exec.batched_ckks.warm``).  Nothing to do on the CPU, for the
        scalar engine or another protocol, or for a plan shape this
        process has prepared before."""
        from .kernels import use_pallas
        spec = self.spec
        if self.protocol != "ckks" or spec.exec_backend == "scalar" \
                or not use_pallas():
            return
        key = (spec.plan_hash(self.workload), spec.plan_mode,
               spec.exec_backend)
        if key in _PREPARED:
            return
        from .exec.batched_ckks import warm
        planned = self.plan()
        warm(self.ckks_params(),
             self._batch_schedules(planned) if spec.exec_backend == "batched"
             else self._overlap_schedules(planned))
        _PREPARED.add(key)

    # -- stage 3a: execute -----------------------------------------------------

    def _driver_name(self, real: bool | None) -> str:
        if real is None or self.protocol != "gc":
            return self.spec.driver      # CKKS is real crypto either way
        return "gc-2party" if real else "gc-plaintext"

    def execute(self, real: bool | None = None,
                check: bool = False) -> dict[int, np.ndarray]:
        """Run the planned programs through the engine; returns the merged
        ``tag → value`` outputs of the endpoints THIS process hosts.
        ``real`` overrides the spec's driver for GC (True → two-party
        crypto, False → plaintext oracle).

        Placement comes from the spec's transport/fabric: the default
        hosts every (party, worker) engine here on threads over the
        ``inproc`` backend; a spec with ``fabric.rank=k`` runs exactly
        one engine against remote peers (distributed mode — outputs are
        then partial, so ``check`` is refused)."""
        planned = self.plan()
        spec = self.spec
        ddef = _driver_def(self._driver_name(real))
        try:
            make_storage = STORAGE_BACKENDS[spec.storage]
        except KeyError:
            raise KeyError(f"unknown storage {spec.storage!r}; registered: "
                           f"{sorted(STORAGE_BACKENDS)}") from None

        p = spec.num_workers
        fx = build_fabric(spec.transport, ddef.parties * p, spec.fabric)
        if check and fx.distributed:
            raise ValueError("check=True needs the full outputs; a "
                             "distributed rank only holds its own (run "
                             "`python -m repro fabric` for a checked fleet)")
        scheds = self._batch_schedules(planned) \
            if spec.exec_backend == "batched" else None
        oscheds = self._overlap_schedules(planned) \
            if spec.exec_backend == "overlap" else None
        outputs: dict[int, np.ndarray] = {}
        try:
            fx.connect()
            drivers = ddef.factory(self, fx)
            jobs = []
            for r in sorted(drivers):
                party, wk = divmod(r, p)
                drv = drivers[r]
                if scheds is not None or oscheds is not None:
                    # overlap reuses the batched drivers for its K_LOCAL
                    # groups, so both backends wrap the scalar driver
                    from .exec import make_batched
                    drv = make_batched(drv)
                prog = planned[wk]
                storage = make_storage((prog.page_slots, drv.lane),
                                       drv.dtype)
                jobs.append(EngineJob(prog, drv,
                                      net=fx.view(r, party * p, p),
                                      storage=storage,
                                      batch_schedule=(scheds[wk] if scheds
                                                      else None),
                                      overlap_schedule=(oscheds[wk]
                                                        if oscheds
                                                        else None),
                                      tag=f"party{party}/worker{wk}"))
            self.engine_stats = run_engines(jobs)
            if fx.distributed:
                # hold the process until every peer drained its traffic
                fx.barrier()
            self.transport_stats = fx.stats()
            for d in drivers.values():
                outputs.update(getattr(d, "outputs", {}))
        finally:
            fx.close()
        if check:
            self.check(outputs)
        return outputs

    def _batch_schedules(self, planned) -> list:
        """One exec/ batch schedule per worker memory program, served from
        the artifact cache when possible (see docs/ENGINE.md).

        Keyed by ``plan_hash`` like the plan entry it describes.  Unbounded
        runs build in-process: ``plan_mode`` is excluded from the plan hash
        (the planned pipelines are output-identical), but an unbounded
        "plan" is the raw trace, so its sidecar would collide with the
        memory-mode entry of the same spec."""
        from .exec.batching import build_batch_schedule
        spec = self.spec
        cache = self._usable_cache()
        if cache is not None and spec.plan_mode != "unbounded":
            got = cache.get_batch(spec, self.workload)
            if got is not None and len(got) == len(planned):
                self.cache_events["batch"] = "hit"
                return got
            self.cache_events["batch"] = "miss"
            scheds = [build_batch_schedule(p, spec.chunk_instrs)
                      for p in planned]
            cache.put_batch(spec, self.workload, scheds)
            return scheds
        return [build_batch_schedule(p, spec.chunk_instrs) for p in planned]

    def _overlap_schedules(self, planned) -> list:
        """One exec/ overlap schedule per worker memory program, served
        from the artifact cache when possible (docs/OVERLAP.md).  Same
        keying and unbounded-mode caveat as ``_batch_schedules``."""
        from .exec.overlap import build_overlap_schedule
        spec = self.spec
        cache = self._usable_cache()
        if cache is not None and spec.plan_mode != "unbounded":
            got = cache.get_overlap(spec, self.workload)
            if got is not None and len(got) == len(planned):
                self.cache_events["overlap"] = "hit"
                return got
            self.cache_events["overlap"] = "miss"
            scheds = [build_overlap_schedule(p, spec.chunk_instrs)
                      for p in planned]
            cache.put_overlap(spec, self.workload, scheds)
            return scheds
        return [build_overlap_schedule(p, spec.chunk_instrs)
                for p in planned]

    # -- stage 3b: simulate ----------------------------------------------------

    def simulate(self, cost_fn: Callable, model: DeviceModel | None = None,
                 os_page_bytes: int | None = None,
                 slot_bytes: int | None = None,
                 core: str | None = None) -> list[WorkerScenarios]:
        """Replay the three §8.2 scenarios (Unbounded / OS swap / MAGE)
        per worker with the given per-instruction cost model.

        ``core`` overrides the spec's ``sim_core``: ``"array"`` (default)
        replays record chunks through the vectorized simulator cores —
        pricing whole chunks with ``cost_fn.cost_chunk`` when the cost
        object provides one — while ``"scalar"`` runs the per-instruction
        reference loops.  Results are exactly equal either way (see
        docs/SIMULATOR.md)."""
        if self.spec.plan_mode == "unbounded":
            raise ValueError("simulate() compares scenarios under a memory "
                             "budget; plan_mode='unbounded' has none")
        progs = self.trace()
        planned = self.plan()
        if any(c is None for c in self._cfgs):
            raise ValueError(
                "simulate() needs the plan configs and reports of an "
                "in-session plan(); a Session loaded with from_plan() can "
                "only execute() its artifacts")
        sb = slot_bytes if slot_bytes is not None else SLOT_BYTES[self.protocol]
        sim_core = core if core is not None else self.spec.sim_core
        chunk = self.spec.chunk_instrs
        out = []
        for wk, prog in enumerate(progs):
            page_bytes = prog.page_slots * sb
            cfg = self._cfgs[wk]
            ub = simulate_unbounded(prog, cost_fn, core=sim_core,
                                    chunk_instrs=chunk)
            osr = simulate_os_paging(prog, cost_fn, cfg.num_frames,
                                     page_bytes, model,
                                     os_page_bytes=os_page_bytes,
                                     core=sim_core, chunk_instrs=chunk)
            mem = planned[wk]
            mage = simulate_memory_program(mem, cost_fn, page_bytes, model,
                                           core=sim_core, chunk_instrs=chunk)
            if isinstance(mem, ProgramFile):
                nbytes = os.path.getsize(mem.path)
            else:
                from .core.bytecode import RECORD_BYTES
                nbytes = len(mem) * RECORD_BYTES
            out.append(WorkerScenarios(
                unbounded=ub, os=osr, mage=mage,
                report=self.plan_reports[wk], config=cfg,
                working_set_pages=self.working_set(wk),
                page_bytes=page_bytes, instructions=len(prog),
                program_bytes=nbytes))
        return out

    # -- plan artifacts --------------------------------------------------------

    def save_plan(self, outdir: str | os.PathLike) -> str:
        """Write the planned memory programs + a ``job.json`` manifest to
        ``outdir``; returns the manifest path.  Streaming plan files are
        moved (they can be far larger than RAM), in-memory plans are
        serialized."""
        outdir = os.fspath(outdir)
        os.makedirs(outdir, exist_ok=True)
        planned = self.plan()
        names = []
        cache_hit = self.cache_events.get("plan") == "hit"
        for i, p in enumerate(planned):
            dst = os.path.join(outdir, f"worker{i}.memory.bc")
            if isinstance(p, ProgramFile):
                if os.path.abspath(p.path) != os.path.abspath(dst):
                    if cache_hit:
                        # cache-resident artifacts stay in the cache
                        shutil.copyfile(p.path, dst)
                    else:
                        shutil.move(p.path, dst)
                        srcdir = os.path.dirname(p.path)
                        if not os.listdir(srcdir):
                            os.rmdir(srcdir)
                planned[i] = ProgramFile(dst)
            else:
                planned[i] = write_program(p, dst)
            names.append(os.path.basename(dst))
        manifest = {"format": 1, "spec": self.spec.to_dict(),
                    "spec_hash": self.spec.plan_hash(self.workload),
                    "programs": names}
        path = os.path.join(outdir, JOB_FILE)
        with open(path, "w") as f:
            json.dump(manifest, f, indent=2)
        return path

    @classmethod
    def from_plan(cls, jobdir: str | os.PathLike,
                  storage: str | None = None,
                  driver: str | None = None,
                  transport: str | None = None,
                  fabric: FabricSpec | None = None) -> "Session":
        """Load a saved plan for direct execution.

        The spec hash is recomputed from the manifest's spec and validated
        against both the manifest and every program file's stamped meta —
        a mismatch (edited job.json, swapped plan files, changed planner
        semantics) raises :class:`SpecMismatchError` instead of executing
        a stale plan.  ``storage``/``driver``/``transport``/``fabric``
        override execution details (which are excluded from the hash by
        design) — the same artifact runs in-process or as one rank of a
        TCP fleet."""
        jobdir = os.fspath(jobdir)
        with open(os.path.join(jobdir, JOB_FILE)) as f:
            manifest = json.load(f)
        spec = JobSpec.from_dict(manifest["spec"])
        expect = spec.plan_hash()
        if manifest.get("spec_hash") != expect:
            raise SpecMismatchError(
                f"job.json spec hashes to {expect} but manifest claims "
                f"{manifest.get('spec_hash')} — spec was modified after "
                f"planning; re-run `plan`")
        overrides = {}
        if storage is not None:
            overrides["storage"] = storage
        if driver is not None:
            overrides["driver"] = driver
        if transport is not None:
            overrides["transport"] = transport
        if fabric is not None:
            overrides["fabric"] = fabric
        if overrides:
            spec = dataclasses.replace(spec, **overrides)
        sess = cls(spec)
        names = manifest["programs"]
        if len(names) != sess.spec.num_workers:
            raise SpecMismatchError(
                f"{len(names)} program files for "
                f"{sess.spec.num_workers} workers")
        planned = []
        for name in names:
            pf = ProgramFile(os.path.join(jobdir, name))
            got = pf.meta.get("spec_hash")
            if got != expect:
                raise SpecMismatchError(
                    f"{name} was planned for spec {got}, job.json says "
                    f"{expect} — artifact and spec disagree")
            planned.append(pf)
        sess._planned = planned
        sess._cfgs = [None] * len(planned)
        return sess


# ---------------------------------------------------------------------------
# oracle check
# ---------------------------------------------------------------------------


def check_outputs(w: Workload, n: int, outputs: dict[int, np.ndarray],
                  atol: float = 2e-2, **extra) -> None:
    """Compare executed outputs against the workload's numpy oracle;
    ``extra`` is what the oracle is built from (``workload_extra``)."""
    exp = w.oracle(n, **extra)
    missing = set(exp) - set(outputs)
    assert not missing, f"{w.name}: missing outputs {sorted(missing)[:5]}..."
    for tag, e in exp.items():
        got = outputs[tag]
        if w.protocol in ("gc", "shamir"):
            assert np.array_equal(got, e), \
                f"{w.name} tag {tag}: {got[:4]} != {e[:4]}"
        else:
            err = np.max(np.abs(np.asarray(got) - e))
            assert err < atol, f"{w.name} tag {tag}: err {err}"


def run_job(spec: JobSpec, real: bool | None = None,
            check: bool = False, cache=None) -> dict[int, np.ndarray]:
    """One-shot convenience: trace, plan, execute, clean up."""
    with Session(spec, cache=cache) as s:
        return s.execute(real=real, check=check)


def plan(spec: JobSpec, outdir: str | os.PathLike, cache=None) -> str:
    """One-shot plan: trace + plan ``spec`` (cache-aware when ``cache``
    is an ArtifactCache or cache-root path) and save the memory programs
    plus ``job.json`` manifest to ``outdir``; returns the manifest path.

    The blessed top-level entry point (``repro.plan``) mirroring
    ``python -m repro plan``; execute the artifacts later with
    :meth:`Session.from_plan` or ``python -m repro run``."""
    with Session(spec, cache=cache) as s:
        return s.save_plan(outdir)


# ---------------------------------------------------------------------------
# admission sizing (the serving daemon's resource model)
# ---------------------------------------------------------------------------


def estimate_job_resources(sess: Session) -> tuple[int, int]:
    """(frames, bytes) one job will pin while planning and executing.

    Frames are the paper's T summed over workers — resolved from a
    cached plan's configs when available (zero tracing), directly from
    an integer budget, or by tracing for working-set-fractional budgets.
    Bytes add the planner's O(frames) peak estimate
    (:func:`repro.core.planner.plan_memory_estimate`) to the engine's
    resident frame memory (frames x page bytes x parties).  This is what
    the serving daemon's admission controller charges per tenant."""
    from .core.planner import plan_memory_estimate
    spec = sess.spec
    cfgs: list[PlanConfig] | None = None
    if spec.plan_mode == "unbounded":
        # no plan: the engine keeps the whole working set resident
        frames_w = [sess.working_set(i) for i in range(spec.num_workers)]
    elif sess.plan_if_cached():
        cfgs = [c for c in sess._cfgs if c is not None]
        frames_w = [c.num_frames for c in cfgs]
        cfgs = None                     # planning is skipped on a hit
    elif not isinstance(spec.memory_budget, float):
        cfgs = [resolve_plan_config(spec, None)] * spec.num_workers
        frames_w = [c.num_frames for c in cfgs]
    else:
        cfgs = [resolve_plan_config(spec, p, self_ws)
                for p, self_ws in ((sess.trace()[i], sess.working_set(i))
                                   for i in range(spec.num_workers))]
        frames_w = [c.num_frames for c in cfgs]
    frames = sum(frames_w)
    page_shift = sess.workload.page_shift_for(**sess.workload_extra())
    page_bytes = (1 << page_shift) * SLOT_BYTES[sess.protocol]
    parties = driver_parties(spec.driver) if spec.driver in DRIVERS else 1
    engine_bytes = frames * page_bytes * parties
    planner_bytes = sum(plan_memory_estimate(c, spec.chunk_instrs)
                        for c in cfgs) if cfgs else 0
    return frames, engine_bytes + planner_bytes
