"""Parallel/distributed MAGE execution (§5.1–§5.2, §6 "per-worker planning").

Workers follow the paper's distributed-memory model: each worker is one
thread of computation with its own MAGE-physical address space; DSL programs
are parameterized by (worker_id, num_workers) and express data movement with
explicit network directives.  Planning is run once per worker, independently
— each worker's accesses touch only its own region, so the memory programs
are generated in isolation, in parallel threads, or in parallel *processes*
(programs and plan artifacts are picklable; processes dodge the GIL for the
Python-heavy planner cores).

``run_engines`` is the single worker-orchestration core: every execution
path in the repo (plaintext oracle runs, real two-party GC, CKKS, the
``repro.api.Session`` facade) builds a list of :class:`EngineJob` and hands
it here, so thread spawning and error collection live in exactly one place.
"""

from __future__ import annotations

import concurrent.futures as cf
import contextvars
import dataclasses
import itertools
import multiprocessing
import os
import threading
from typing import Any, Callable, Sequence

import numpy as np

from .bytecode import Op, Program, ProgramFile
from .dsl import Value, trace
from .engine import Engine, EngineStats, ProtocolDriver
from .planner import PlanConfig, PlanReport, plan, plan_streaming
from .storage import StorageBackend
from .transport import InprocTransport, PartyView


@dataclasses.dataclass
class ProgramOptions:
    """Mirrors the paper's ProgramOptions: worker identity + problem params."""
    worker: int = 0
    num_workers: int = 1
    problem_size: int = 0
    extra: dict = dataclasses.field(default_factory=dict)


def send_value(v: Value, dst: int, tag: int | None = None) -> int:
    b = v.builder
    tag = b.fresh_tag() if tag is None else tag
    b.emit(Op.NET_SEND, ins=(v.span,), imm=(dst, tag))
    return tag


def recv_into(v: Value, src: int, tag: int) -> None:
    v.builder.emit(Op.NET_RECV, outs=(v.span,), imm=(src, tag))


def trace_workers(fn: Callable[[ProgramOptions], None], *, protocol: str,
                  page_shift: int, num_workers: int,
                  problem_size: int = 0, extra: dict | None = None,
                  meta: dict | None = None) -> list[Program]:
    progs = []
    for w in range(num_workers):
        opts = ProgramOptions(worker=w, num_workers=num_workers,
                              problem_size=problem_size,
                              extra=dict(extra or {}))
        progs.append(trace(fn, protocol=protocol, page_shift=page_shift,
                           worker=w, num_workers=num_workers,
                           args=(opts,),
                           meta={"problem_size": problem_size,
                                 **(meta or {})}))
    return progs


# ---------------------------------------------------------------------------
# per-worker planning
# ---------------------------------------------------------------------------

PARALLEL_MODES = ("serial", "thread", "process")


def _plan_one(w: int, prog: Program | ProgramFile, cfg: PlanConfig,
              streaming: bool, workdir: str | None, track_memory: bool,
              chunk_instrs: int, annotation: str | None,
              ) -> tuple[Program | ProgramFile, PlanReport]:
    """Module-level so ``parallel="process"`` can pickle it."""
    if streaming:
        wd = os.path.join(workdir, f"worker{w}") if workdir else None
        return plan_streaming(prog, cfg, workdir=wd,
                              track_memory=track_memory,
                              chunk_instrs=chunk_instrs,
                              annotations=annotation)
    return plan(prog, cfg, track_memory=track_memory)


def plan_workers(progs: Sequence[Program], cfg: PlanConfig | Sequence[PlanConfig],
                 parallel: bool | str = False, streaming: bool = False,
                 workdir: str | None = None, track_memory: bool = False,
                 chunk_instrs: int = 8192,
                 annotations: Sequence[str] | None = None,
                 ) -> tuple[list[Program | ProgramFile], list[PlanReport]]:
    """Plan each worker's program independently (§6.1).

    Worker programs only touch their own address space, so planning them is
    embarrassingly parallel.  ``parallel`` selects the executor: ``False`` /
    ``"serial"`` plans in-line, ``True`` / ``"thread"`` runs one planner
    thread per worker, and ``"process"`` uses a ``ProcessPoolExecutor`` to
    dodge the GIL for the Python-heavy planner cores (programs, configs and
    ProgramFiles are all picklable).  ``streaming=True`` uses the out-of-core
    file pipeline (one subdirectory per worker) and returns ProgramFiles the
    engine executes directly from disk.  ``cfg`` may be a single PlanConfig
    or one per worker (budgets can differ per working set).

    ``track_memory=True`` with ``parallel="thread"`` plans serially instead:
    tracemalloc is process-global, so concurrent planner threads would reset
    each other's measurement (``"process"`` keeps both parallelism and
    per-worker peaks).

    ``annotations`` — optional per-worker pre-computed next-use sidecar
    paths (streaming only), e.g. from the artifact cache; the annotation
    pass is skipped for workers that have one.
    """
    cfgs = list(cfg) if isinstance(cfg, (list, tuple)) else [cfg] * len(progs)
    if len(cfgs) != len(progs):
        raise ValueError(f"{len(cfgs)} configs for {len(progs)} workers")
    anns = list(annotations) if annotations is not None \
        else [None] * len(progs)
    if len(anns) != len(progs):
        raise ValueError(f"{len(anns)} annotations for {len(progs)} workers")
    mode = {False: "serial", True: "thread"}.get(parallel, parallel)
    if mode not in PARALLEL_MODES:
        raise ValueError(f"parallel must be one of {PARALLEL_MODES}, "
                         f"got {parallel!r}")
    if track_memory and mode == "thread":
        # tracemalloc is process-global: concurrent start/stop from planner
        # threads would reset each other's measurement. Processes are fine.
        mode = "serial"
    args = (range(len(progs)), progs, cfgs, itertools.repeat(streaming),
            itertools.repeat(workdir), itertools.repeat(track_memory),
            itertools.repeat(chunk_instrs), anns)
    if mode == "serial" or len(progs) <= 1:
        results = list(map(_plan_one, *args))
    elif mode == "thread":
        with cf.ThreadPoolExecutor(max_workers=len(progs),
                                   thread_name_prefix="mage-plan") as ex:
            results = list(ex.map(_plan_one, *args))
    else:
        # spawn, never fork: a forked child would inherit a parent's
        # claim on the accelerator (e.g. the serve daemon after a job ran)
        with cf.ProcessPoolExecutor(
                max_workers=len(progs),
                mp_context=multiprocessing.get_context("spawn")) as ex:
            results = list(ex.map(_plan_one, *args))
    return [r[0] for r in results], [r[1] for r in results]


# ---------------------------------------------------------------------------
# the worker-orchestration core
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class EngineJob:
    """One engine to run: a (program, driver) pair plus its fabric/storage.

    ``net`` is the engine's party-scoped window onto the transport fabric
    (NET_* directives); ``tag`` is only used to label failures (e.g.
    ``"garbler/worker1"``).
    """
    program: Program | ProgramFile
    driver: ProtocolDriver
    net: PartyView | None = None
    storage: StorageBackend | None = None
    use_memmap: bool = False
    on_output: Callable | None = None
    tag: Any = None
    #: optional exec/ batch schedule (see repro.exec.batching); engines run
    #: the batched fast path when both this and a batch-capable driver are
    #: present, the scalar reference loop otherwise
    batch_schedule: Any = None
    #: optional exec/ overlap schedule (see repro.exec.overlap); takes
    #: precedence over batch_schedule — the overlap loop batches local
    #: groups itself when the driver is batch-capable
    overlap_schedule: Any = None


def run_engines(jobs: Sequence[EngineJob],
                io_threads: int = 2) -> list[EngineStats]:
    """Run one Engine per job, concurrently; THE thread-spawn/error-collect
    loop (every other runner is a wrapper over this)."""
    results: list[EngineStats | None] = [None] * len(jobs)
    errors: list[tuple[Any, Exception]] = []

    def _run(k: int, job: EngineJob) -> None:
        try:
            eng = Engine(job.program, job.driver, storage=job.storage,
                         net=job.net, io_threads=io_threads,
                         use_memmap=job.use_memmap,
                         batch_schedule=job.batch_schedule,
                         overlap_schedule=job.overlap_schedule)
            results[k] = eng.run(on_output=job.on_output)
        except Exception as e:  # surfaced below
            errors.append((job.tag if job.tag is not None else k, e))

    if len(jobs) == 1:
        _run(0, jobs[0])
    else:
        # each engine thread runs in a copy of the caller's context, so
        # what it records carries the caller's job (repro.obs)
        threads = [threading.Thread(target=contextvars.copy_context().run,
                                    args=(_run, k, job), daemon=True)
                   for k, job in enumerate(jobs)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    if len(errors) == 1:
        raise errors[0][1]          # sole failure: original exception type
    if errors:
        tags = [t for t, _ in errors]
        raise RuntimeError(f"engine failures in {tags}: {errors}") \
            from errors[0][1]
    return results


def run_workers(progs: Sequence[Program | ProgramFile],
                driver_factory: Callable[[int], ProtocolDriver],
                use_memmap: bool = False,
                on_output: Callable[[int, Any, list[np.ndarray]], None] | None = None,
                ) -> list:
    """Run one engine per worker on threads sharing an inproc fabric."""
    net = PartyView(InprocTransport(len(progs)), 0, len(progs))
    jobs = []
    for w, p in enumerate(progs):
        cb = (lambda i, v, _w=w: on_output(_w, i, v)) if on_output else None
        jobs.append(EngineJob(p, driver_factory(w), net=net,
                              use_memmap=use_memmap, on_output=cb,
                              tag=f"worker{w}"))
    return run_engines(jobs)
