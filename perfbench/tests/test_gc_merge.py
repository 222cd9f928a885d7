"""A two-party garbled-circuit merge, brought to the harness as files
alone: a configuration (``data/gc-merge-tiny.json``) that names the
``merge`` reference, in a checkout whose BENCHMARK.json lists only it.
Whole runs on the CPU at n=64 records a party, with the look for a chip
skipped; each fault of the timed path has to make ``correct`` false.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests/test_gc_merge.py
"""

import os
import sys
import threading
import time

import pytest

import harness
import judge
import traffic
from test_faults import DEV, patched

SEED = 2**31 + 2**30 + 4242
OUT = 1 << 24


def run(c, seconds: float = 2.0) -> dict:
    return harness.run(c, SEED, seconds, False, time.perf_counter(), DEV)


def plaintext(c):
    c.config["job"]["driver"] = "gc-plaintext"
    return c


def test_cell_needs_no_edit_of_the_benchmark(gc_cell):
    assert gc_cell.config["reference"] == "merge"
    assert gc_cell.traffic["name"] == "closed.b40"
    assert [m["name"] for m in gc_cell.per_layer] == ["device.idle_share"]


def test_plaintext_run_is_correct(gc_cell):
    r = run(plaintext(gc_cell))
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 2 and r["failed"] == 0
    assert list(r["checks"]) == ["mismatched", "missing", "failed",
                                 "frames_over"]


def test_two_party_job_is_correct_and_both_ranks_drew_it(gc_cell):
    with harness.Stand(gc_cell, SEED, trace=False) as stand:
        win = stand.drive(1e-3)                 # one job
        assert [j["index"] for j in win.jobs] == [0]
        assert stand.workload.drawn == [0, 0]   # garbler and evaluator
        checks = stand.checks(win)
    assert judge.passed(checks), checks
    assert checks["mismatched"]["value"] == 0


def test_ranks_of_a_job_share_one_draw(gc_cell):
    """More ranks than cores ask at once: one draw a job, every rank
    handed the same values."""
    inputs = traffic.Inputs(gc_cell.config, SEED)
    draws, provide = [], inputs.provider
    inputs.provider = lambda job: draws.append(job) or provide(job)
    w = harness.Workload(gc_cell.config, inputs)
    ranks = min(2 * (os.cpu_count() or 4), 256)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for k in range(3):
            assert w.start() == k
            got = []
            threads = [threading.Thread(
                target=lambda: got.append(w._provider(64, 0, 1)))
                for _ in range(ranks)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            assert not any(t.is_alive() for t in threads)
            assert w.drawn == [k] * ranks and draws == list(range(k + 1))
            assert len(got) == ranks and all(g is got[0] for g in got)
    finally:
        sys.setswitchinterval(interval)


def test_a_rank_that_draws_another_job_stops_the_run(gc_cell):
    """Each rank has to draw the job being submitted, once."""
    def make(orig):
        def provider(w, *a, **kw):
            w.drawn.append(-1)
            return orig(w, *a, **kw)
        return provider
    with patched(harness.Workload, "_provider", make):
        with pytest.raises(RuntimeError, match="ranks drew"):
            run(plaintext(gc_cell))


def test_one_bit_flipped_where_produced(gc_cell):
    from repro.core.bytecode import Op
    from repro.protocols.garbled.driver import PlaintextDriver

    def make(orig):
        def execute(drv, op, imm, outs, ins):
            orig(drv, op, imm, outs, ins)
            if op == Op.OUTPUT and imm[2] == OUT + 1:   # record 5's payload
                drv.outputs[imm[2]] = drv.outputs[imm[2]].copy()
                drv.outputs[imm[2]][5] ^= 1 << 40
        return execute
    with patched(PlaintextDriver, "execute", make):
        r = run(plaintext(gc_cell))
    assert not r["correct"]
    assert r["checks"]["mismatched"]["value"] > 0
    assert r["checks"]["missing"]["value"] == 0


def test_one_output_chunk_dropped(gc_cell):
    from repro.core.bytecode import Op
    from repro.protocols.garbled.driver import PlaintextDriver

    def make(orig):
        def execute(drv, op, imm, outs, ins):
            if not (op == Op.OUTPUT and imm[2] == OUT + 2):
                orig(drv, op, imm, outs, ins)
        return execute
    with patched(PlaintextDriver, "execute", make):
        r = run(plaintext(gc_cell))
    assert not r["correct"]
    assert r["checks"]["missing"]["value"] > 0


def test_job_returning_the_previous_jobs_outputs(gc_cell):
    from repro.api import Session
    last = {}

    def make(orig):
        def execute(sess, *a, **kw):
            out = orig(sess, *a, **kw)
            stale = dict(last) or out
            last.clear()
            last.update(out)
            return stale
        return execute
    with patched(Session, "execute", make):
        r = run(plaintext(gc_cell))
    assert r["attempted"] >= 2
    assert not r["correct"]
    assert r["checks"]["mismatched"]["value"] > 0


def test_warm_kernels_warms_nothing_without_a_ckks_ring(gc_cell,
                                                        monkeypatch):
    import repro.kernels
    from repro.api import JobSpec
    monkeypatch.setattr(repro.kernels, "use_pallas", lambda: True)
    spec = JobSpec(**{**gc_cell.job_fields(), "workload": "merge"})
    assert spec.ckks_ring is None
    assert harness.warm_kernels(spec, []) == []
