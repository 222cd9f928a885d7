"""The program's own recorder (``repro.obs``) on daemon jobs.

Off, a job leaves the recorder empty, builds no ``TraceAnnotation`` and
reads no clock inside the engine loop.  On, one job on two workers gives
spans that carry the job's id on every thread and nest, counters that agree
with the engine's own counts and with a count of the NTT kernel's launches
taken from outside, and ``storage.wait`` only inside a swap directive.

CPU, ring N=128; the batched CKKS driver's multiplies take its device
chain, with the Pallas kernels in interpret mode, so that launches happen.
"""

import sys
import threading
import time

import pytest

from repro import obs
from repro.api import JobSpec, Session
from repro.core.engine import Engine
from repro.core.storage import AsyncIO
from repro.exec import batched_ckks
from repro.kernels.ntt import kernel
from repro.kernels.ntt import ops as ntt_ops
from repro.protocols.ckks.params import CkksParams
from repro.serve_daemon.client import serve_client
from repro.serve_daemon.server import ServeDaemon

SPEC = {"workload": "n_rmatmul", "n": 4, "num_workers": 2,
        "ckks_ring": 128, "ckks_levels": 2, "exec_backend": "batched",
        "plan_mode": "memory", "memory_budget": 0.4, "prefetch_pages": 2}
#: one worker whose 0.4 budget (10 frames) makes the plan swap
SWAPPING = JobSpec(workload="n_rmatmul", n=8, ckks_ring=128, ckks_levels=2,
                   exec_backend="batched", plan_mode="memory",
                   memory_budget=0.4, prefetch_pages=2, lookahead=100)
CLOCKS = ("time", "time_ns", "perf_counter", "perf_counter_ns", "monotonic",
          "monotonic_ns")


@pytest.fixture()
def recorder():
    obs.disable()
    obs.drain()
    yield obs
    obs.disable()
    obs.drain()


@pytest.fixture()
def daemon(tmp_path):
    d = ServeDaemon(tmp_path / "cache", socket_path=str(tmp_path / "d.sock"))
    d.start()
    yield d
    d.shutdown()


@pytest.fixture()
def launches(monkeypatch):
    """Multiplies of the batched CKKS driver on its device chain, each
    launch's shape and direction taken by wrapping ``kernel.ntt_pallas``.
    The chain's twiddle tables are on the device before the job starts."""
    shapes = []
    orig = kernel.ntt_pallas

    def counted(a, *args, **kw):
        shapes.append((*a.shape, kw.get("inverse", False)))
        return orig(a, *args, **kw)

    monkeypatch.setattr(kernel, "ntt_pallas", counted)
    monkeypatch.setattr(batched_ckks, "use_pallas", lambda: True)
    for q in CkksParams(n_ring=SPEC["ckks_ring"],
                        levels=SPEC["ckks_levels"]).primes:
        ntt_ops.device_tables(q, SPEC["ckks_ring"])
    return shapes


def submit(daemon) -> dict:
    with serve_client(daemon.address) as c:
        resp = c.submit(SPEC, execute=True, return_outputs=True)
        c.ping()        # answered once the job's last span has closed
    return resp


def test_off_records_nothing_and_builds_no_annotation(recorder, daemon,
                                                      launches, monkeypatch):
    import jax.profiler

    class Refused(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **kw):
            raise AssertionError("TraceAnnotation built with the recorder off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Refused)
    resp = submit(daemon)
    assert resp["ok"] and resp["outputs"] and launches
    assert set(resp["timings"]) == {"queued_s", "plan_s", "execute_s",
                                    "total_s"}
    assert resp["timings"]["total_s"] >= resp["timings"]["execute_s"] > 0
    rec = recorder.drain()
    assert rec.spans == [] and rec.counts == {}


def test_off_reads_no_clock_in_the_engine_loop(recorder, launches,
                                               monkeypatch):
    inside = threading.local()
    reads = []

    def watched(orig):
        def clock():
            caller = sys._getframe(1).f_globals.get("__name__", "")
            if getattr(inside, "on", False) and caller.startswith("repro"):
                reads.append(caller)
            return orig()
        return clock

    for name in CLOCKS:
        monkeypatch.setattr(time, name, watched(getattr(time, name)))
    run = Engine.run

    def flagged(eng, *args, **kw):
        inside.on = True
        try:
            return run(eng, *args, **kw)
        finally:
            inside.on = False

    monkeypatch.setattr(Engine, "run", flagged)
    with Session(JobSpec(**SPEC)) as s:
        s.execute()
    assert reads == [] and launches
    # the watch sees the recorder's own readings once it is on
    recorder.enable()
    with Session(JobSpec(**SPEC)) as s:
        s.execute()
    assert "repro.obs" in reads


def _contains(outer, inner) -> bool:
    return outer.t0_ns <= inner.t0_ns and inner.t1_ns <= outer.t1_ns


def test_on_one_job_on_two_workers(recorder, daemon, launches, monkeypatch):
    stats, batchable = [], []
    run, one, batch = Engine.run, Engine._exec_one, Engine._exec_batch

    def run_kept(eng, *args, **kw):
        stats.append(run(eng, *args, **kw))
        return stats[-1]

    def one_seen(eng, instr, *args):
        if instr.op in eng.driver.batch_ops:
            batchable.append(1)
        return one(eng, instr, *args)

    def batch_seen(eng, op, rec, rows):
        batchable.append(len(rows))
        return batch(eng, op, rec, rows)

    monkeypatch.setattr(Engine, "run", run_kept)
    monkeypatch.setattr(Engine, "_exec_one", one_seen)
    monkeypatch.setattr(Engine, "_exec_batch", batch_seen)
    recorder.enable()
    resp = submit(daemon)
    recorder.disable()
    rec = recorder.drain()
    job = resp["job_id"]
    names = {s.name for s in rec.spans}
    assert {"daemon.job", "daemon.session", "daemon.admit", "daemon.plan",
            "daemon.execute", "daemon.encode", "daemon.send", "engine.run",
            "ckks.CT_RELIN", "batched.CT_MUL_NR", "ntt.forward",
            "ntt.inverse"} <= names
    assert "ckks.CT_MUL_NR" not in names      # every multiply on the chain

    # every span is the job's, on the engine threads too
    assert {s.job for s in rec.spans} == {job}
    runs = [s for s in rec.spans if s.name == "engine.run"]
    (root,) = [s for s in rec.spans if s.name == "daemon.job"]
    assert len(runs) == 2 and len({s.thread for s in runs}) == 2
    assert root.thread not in {s.thread for s in runs}
    assert all(s.parent == "daemon.execute" for s in runs)

    # on each thread spans nest, and each names its innermost enclosure
    for thread in {s.thread for s in rec.spans}:
        mine = [s for s in rec.spans if s.thread == thread]
        for s in mine:
            around = [o for o in mine if o is not s and _contains(o, s)]
            for o in mine:
                if o is not s:
                    assert (o.t1_ns <= s.t0_ns or s.t1_ns <= o.t0_ns
                            or _contains(o, s) or _contains(s, o)), (o, s)
            if around:
                inner = min(around, key=lambda o: o.t1_ns - o.t0_ns)
                assert s.parent == inner.name, (s, inner)
    for name in ("daemon.encode", "daemon.send"):
        (s,) = [s for s in rec.spans if s.name == name]
        assert _contains(root, s) and s.parent == "daemon.job"
    assert resp["timings"]["total_s"] < (root.t1_ns - root.t0_ns) * 1e-9

    # counters agree with what the engine ran and what the kernel launched
    assert len(stats) == 2
    alone = sum(s.batchable_scalar for s in stats)
    batched = sum(s.batched_instructions for s in stats)
    assert alone > 0 and batched > 0
    assert alone + batched == sum(batchable)
    counts = {name: n for (j, name), n in rec.counts.items() if j == job}
    # each chain uploads its forward launches' rows and reads back its
    # inverse launches' rows, once each
    assert counts["ntt.launches"] == len(launches) > 0
    assert counts["ntt.h2d_bytes"] == sum(b * n * 4
                                          for b, n, inv in launches if not inv)
    assert counts["ntt.d2h_bytes"] == sum(b * n * 4
                                          for b, n, inv in launches if inv)


def test_storage_wait_only_inside_a_swap_directive(recorder, monkeypatch):
    issue_read = AsyncIO.issue_read

    class Unfinished:
        """A read the engine always finds still in flight."""

        def __init__(self, fut):
            self.fut = fut

        def done(self):
            return False

        def result(self):
            return self.fut.result()

    monkeypatch.setattr(AsyncIO, "issue_read", lambda io, *a: Unfinished(
        issue_read(io, *a)))
    recorder.enable()
    with obs.job(7), Session(SWAPPING) as s:
        s.execute()
    recorder.disable()
    spans = recorder.drain().spans
    waits = [w for w in spans if w.name == "storage.wait"]
    swaps = [d for d in spans if d.name.startswith("storage.")
             and d.name != "storage.wait"]
    assert waits and swaps and {w.job for w in spans} == {7}
    for w in waits:
        assert w.parent.startswith("storage.") and w.parent != "storage.wait"
        assert any(d.thread == w.thread and _contains(d, w) for d in swaps)
    # each swap-in's finish waits on its read, and is timed doing so
    finishes = [d for d in swaps if d.name == "storage.FINISH_SWAP_IN"]
    assert finishes and all(
        sum(_contains(f, w) for w in waits) == 1 for f in finishes)


def test_on_while_the_profiler_records(recorder, daemon, tmp_path):
    import jax.profiler

    submit(daemon)                      # left over from before the trace
    recorder.enable()
    submit(daemon)
    recorder.disable()
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        traced = submit(daemon)["job_id"]
    finally:
        jax.profiler.stop_trace()
    after = submit(daemon)["job_id"]
    assert not recorder.enabled()
    rec = recorder.records()
    # the trace's recording starts empty and ends with the trace
    assert {s.job for s in rec.spans if s.name == "daemon.job"} == {traced}
    assert after != traced
    assert recorder.drain() == rec      # records() kept what it showed
    assert recorder.records().spans == []
