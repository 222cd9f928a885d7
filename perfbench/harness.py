"""One run of one cell: set-up, the measured window, the check, the line.

Set-up brings up the chip, starts a ``ServeDaemon`` in this process on a
unix socket, registers the cell's workload under a name of the
benchmark's own with the configuration's seeded reference as its input
provider, warms trace, plan and batch schedule into the daemon's artifact
cache, and compiles the kernel shapes that schedule holds.  The window is
one client in a closed loop: ``submit`` with ``execute`` and
``return_outputs`` on, job after job until ``seconds`` have passed; the
job running then is allowed to finish.  After the window every job's
outputs are compared with the plain reference (``judge``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

import device
import judge
import tracing
from cell import Cell, reader
from observe import Watch
from traffic import Inputs

def log(*parts) -> None:
    print("perfbench:", *parts, file=sys.stderr, flush=True)


class Workload:
    """The cell's workload, registered as ``perfbench.<config>``: the
    program's own DSL program and page size, with inputs drawn per job
    from the seed.  ``drive`` says which job it submits (``start``); the
    first rank of the job that asks draws that job's values, every other
    rank gets the same ones, and ``drawn`` lists the job each rank got."""

    def __init__(self, config: dict, inputs: Inputs):
        from repro.workloads import get, register
        base = get(config["job"]["workload"])
        self.name = "perfbench." + config["name"]
        self.inputs = inputs
        self.job = -1               # the job being submitted
        self.drawn: list[int] = []
        self._values = None         # (job, provider) of the last draw
        self._lock = threading.Lock()
        register(dataclasses.replace(base, name=self.name,
                                     inputs=self._provider))

    def start(self) -> int:
        """The next job's index, which every rank that asks draws."""
        self.job += 1
        self.drawn = []
        return self.job

    def _provider(self, n, worker, num_workers, **extra):
        with self._lock:
            if self._values is None or self._values[0] != self.job:
                self._values = (self.job, self.inputs.provider(self.job))
            self.drawn.append(self._values[0])
            return self._values[1]


def warm_kernels(spec, schedules) -> list[tuple[int, int]]:
    """Compile the NTT launches the batch schedules hold: each CT_MUL_NR
    group of two or more, for every prime of the chain, both directions.
    Returns the (group size, ring) pairs warmed: none on the CPU, whose
    numpy NTT compiles nothing, and none for a spec with no CKKS ring."""
    from repro.core.bytecode import Op
    from repro.kernels import use_pallas
    from repro.kernels.ntt import ops
    from repro.protocols.ckks.params import CkksParams
    if not use_pallas() or spec.ckks_ring is None:
        return []
    n = int(spec.ckks_ring)
    primes = CkksParams(n_ring=n, levels=int(spec.ckks_levels)).primes
    sizes = set()
    for s in schedules:
        big = (s.group_op == int(Op.CT_MUL_NR)) & (np.diff(s.bounds) >= 2)
        sizes.update(int(x) for x in np.diff(s.bounds)[big])
    for size in sorted(sizes):
        for q in primes:
            zero = np.zeros((size, n), np.uint64)
            ops.ntt_inverse(ops.ntt_forward(zero, q, interpret=False), q,
                            interpret=False)
    return [(size, n) for size in sorted(sizes)]


def warm_artifacts(client, daemon, spec) -> list:
    """Trace and plan into the daemon's cache (a submit that does not
    execute), then the batch schedule the executes will read."""
    from repro.api import Session
    from repro.exec.batching import build_batch_schedule
    client.submit(spec, execute=False)
    with Session(spec, cache=daemon.cache) as s:
        planned = s.plan()
        scheds = [build_batch_schedule(p, s.spec.chunk_instrs)
                  for p in planned]
        if s.spec.plan_mode != "unbounded":     # unbounded builds per job
            daemon.cache.put_batch(s.spec, s.workload, scheds)
    return scheds


@dataclasses.dataclass
class Window:
    jobs: list[dict]
    failed: int
    t_first: float
    t_last: float


def drive(client, spec, workload: Workload, seconds: float,
          span) -> Window:
    """Closed-loop jobs for ``seconds``.  Each job's outputs are kept as
    floats, or as exact integers where the reference compares exactly."""
    from repro.api import driver_parties
    from repro.serve_daemon.client import ServeError
    ranks = driver_parties(spec.normalized().driver) * spec.num_workers
    kind = object if workload.inputs.compare == "exact" else np.float64
    jobs, failed = [], 0
    t_first = time.perf_counter()
    t_last = t_first
    while t_last - t_first < seconds:
        t_sub = time.perf_counter()
        k = workload.start()
        try:
            with span(tracing.CLIENT):
                resp = client.submit(spec, execute=True,
                                     return_outputs=True)
        except ServeError as e:
            log(f"job failed: {e}")
            failed += 1
            break
        t_last = time.perf_counter()
        if workload.drawn != [k] * ranks:
            raise RuntimeError(f"job {k}: its {ranks} ranks drew inputs "
                               f"of jobs {workload.drawn}")
        jobs.append({
            "index": k, "seconds": t_last - t_sub,
            "timings": resp["timings"],
            "outputs": {int(t): np.asarray(v, kind)
                        for t, v in resp.get("outputs", {}).items()}})
    return Window(jobs, failed, t_first, t_last)


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader gets (``metrics/<name>.py``)."""
    jobs: list[dict]
    executes: list[dict]
    spans: list[tuple[str, int, int]]
    launches: list[tuple[int, int]]
    reduction: "tracing.Reduction | None"
    peaks: dict


def per_layer(cell: Cell, ctx: Context) -> dict:
    out = {}
    for m in cell.per_layer:
        value = reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


class CompileLog:
    """XLA compiles and persistent-cache lookups from now on, as JAX's
    monitoring events report them."""

    def __init__(self):
        import jax
        self.compile_s: list[float] = []
        self.cache: dict[str, int] = {}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s.append(seconds)

    def _event(self, event: str, **kw) -> None:
        if event.startswith("/jax/compilation_cache/"):
            key = event.rsplit("/", 1)[-1]
            self.cache[key] = self.cache.get(key, 0) + 1

    def summary(self) -> str:
        return (f"{len(self.compile_s)} compiles "
                f"({sum(self.compile_s):.3f} s), cache {self.cache}")

    def reset(self) -> None:
        self.compile_s.clear()
        self.cache.clear()


class Stand:
    """The system under test, set up for one cell: daemon, client, the
    registered workload and the observers.  ``close`` stops them all."""

    def __init__(self, cell: Cell, seed: int, trace: bool):
        from repro.api import JobSpec
        from repro.kernels import configure_compile_cache
        from repro.serve_daemon.client import serve_client
        from repro.serve_daemon.server import ServeDaemon
        import jax
        configure_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        self.cell = cell
        self.compiles = CompileLog()
        self.tmp = tempfile.mkdtemp(prefix="perfbench-")
        self.watch = Watch(traced=trace).install()
        self.daemon = self.client = None
        try:
            self.inputs = Inputs(cell.config, seed)
            self.workload = Workload(cell.config, self.inputs)
            self.spec = JobSpec(**{**cell.job_fields(),
                                   "workload": self.workload.name})
            self.daemon = ServeDaemon(
                os.path.join(self.tmp, "cache"),
                socket_path=os.path.join(self.tmp, "daemon.sock"))
            self.daemon.start()
            self.client = serve_client(self.daemon.address)
            t = time.perf_counter()
            scheds = warm_artifacts(self.client, self.daemon, self.spec)
            t_art = time.perf_counter() - t
            t = time.perf_counter()
            warmed = warm_kernels(self.spec, scheds)
            log(f"warm: trace+plan+schedule {t_art:.3f} s, kernels "
                f"{time.perf_counter() - t:.3f} s for NTT groups {warmed}; "
                f"{self.compiles.summary()}")
            self.watch.install_spans()
            self.watch.clear()
            self.compiles.reset()
        except BaseException:
            self.close()
            raise

    def drive(self, seconds: float, span=contextlib.nullcontext) -> Window:
        return drive(self.client, self.spec, self.workload, seconds, span)

    def checks(self, win: Window) -> dict:
        failed = win.failed + (0 if win.jobs else 1)
        return judge.checks(self.cell.config, self.cell.traffic, win.jobs,
                            self.inputs, self.watch.engines,
                            self.watch.executes, failed)

    def stop_serving(self) -> None:
        if self.client is not None:
            self.client.close()
            self.client = None
        if self.daemon is not None:
            self.daemon.shutdown()
            self.daemon = None

    def close(self) -> None:
        self.stop_serving()
        self.watch.close()
        shutil.rmtree(self.tmp, ignore_errors=True)

    def __enter__(self) -> "Stand":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def run(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
        dev: dict) -> dict:
    """One run; returns the result line (without printing it)."""
    with Stand(cell, seed, trace) as stand:
        setup_s = time.perf_counter() - t0
        log(f"set-up {setup_s:.3f} s")
        watch = stand.watch
        if trace:
            log_dir = os.path.join(stand.tmp, "trace")
            tracing.start(log_dir)
        try:
            win = stand.drive(seconds, watch.span if trace
                              else contextlib.nullcontext)
        finally:
            if trace:
                xplane = tracing.stop(log_dir)
        peak = device.memory_peak_bytes(cell.chips)
        for j in win.jobs:
            log(f"job {j['index']}: {j['seconds']:.4f} s "
                f"(daemon {j['timings']})")
        log(f"in the window: {stand.compiles.summary()}")
        stand.stop_serving()
        checks = stand.checks(win)
        dev_out = {k: dev[k] for k in ("platform", "kind", "count")}
        dev_out["memory_peak_bytes"] = peak
        result = {"correct": judge.passed(checks),
                  "attempted": len(win.jobs) + win.failed,
                  "failed": checks["failed"]["value"]}
        if not trace:
            result["metrics"] = {
                "job_s": {"value": (win.t_last - win.t_first)
                          / max(len(win.jobs), 1), "unit": "s"},
                "setup_s": {"value": setup_s, "unit": "s"}}
        else:
            red = tracing.Reduction(tracing.load(xplane))
            ctx = Context(win.jobs, watch.executes, watch.spans,
                          watch.launches, red, dev["peaks"])
            result["metrics"] = per_layer(cell, ctx)
            dev_out["busy_s"] = red.busy_s
            dev_out["window_s"] = red.window_s
            result["breakdown"] = {"device_ops": red.top_ops(10),
                                   "idle_gaps": red.idle_gaps(10)}
        result["device"] = dev_out
        result["checks"] = checks
        return result
