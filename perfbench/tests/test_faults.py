"""A whole run of the harness, with the look for a chip skipped and the
timed path broken underneath: each fault has to make ``correct`` false.

Runs on the CPU at a tiny ring (N=128, 64 slots), where the program's
NTT is numpy's; everything else is the path the window drives: daemon,
client, planner, batched engine, swaps over ``ram`` storage, outputs.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests/test_faults.py
"""

import contextlib
import time

import numpy as np
import pytest

import cell
import harness

SEED = 2**31 + 977
DEV = {"platform": "cpu", "kind": "cpu", "count": 1,
       "peaks": {"hbm_bytes_per_s": 819e9}}


def tiny(name: str, **traffic_job) -> cell.Cell:
    """The cell at N=128 with two prefetch slots, so that its 0.4 budget
    resolves to 10 frames and the plan swaps."""
    c = cell.load(name)
    c.config["job"].update(ckks_ring=128, prefetch_pages=2)
    c.config["slots"] = 64
    c.traffic["job"].update(traffic_job)
    return c


def run(c: cell.Cell, seconds: float = 2.0, trace: bool = False) -> dict:
    return harness.run(c, SEED, seconds, trace, time.perf_counter(), DEV)


@contextlib.contextmanager
def patched(owner, attr, make):
    orig = getattr(owner, attr)
    setattr(owner, attr, make(orig))
    try:
        yield
    finally:
        setattr(owner, attr, orig)


def swap_ins(c: cell.Cell) -> list[int]:
    with harness.Stand(c, SEED, trace=False) as stand:
        stand.drive(0.001)
        return [r.replacement.swap_ins for e in stand.watch.executes
                for r in e["reports"]]


@pytest.mark.parametrize("name", ["nmatmul.b40", "rmvmul.b40",
                                  "nmatmul.resident"])
def test_sound_run_is_correct(name):
    r = run(tiny(name))
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"job_s", "setup_s"}


def test_tiny_budget_swaps():
    assert min(swap_ins(tiny("nmatmul.b40"))) > 0


def test_answer_altered_where_produced():
    from repro.core.bytecode import Op
    from repro.protocols.ckks.driver import CkksDriver

    def make(orig):
        def execute(drv, op, imm, outs, ins):
            orig(drv, op, imm, outs, ins)
            if op == Op.OUTPUT and len(drv.outputs) == 1:
                tag = next(iter(drv.outputs))
                drv.outputs[tag] = drv.outputs[tag].copy()
                drv.outputs[tag][3] += 0.01
        return execute
    with patched(CkksDriver, "execute", make):
        r = run(tiny("nmatmul.b40"))
    assert not r["correct"]
    assert r["checks"]["max_err"]["value"] > r["checks"]["max_err"]["limit"]


def test_half_of_the_outputs_left_out():
    from repro.core.bytecode import Op
    from repro.protocols.ckks.driver import CkksDriver

    def make(orig):
        def execute(drv, op, imm, outs, ins):
            if op == Op.OUTPUT and imm[0] % 2:
                return
            orig(drv, op, imm, outs, ins)
        return execute
    with patched(CkksDriver, "execute", make):
        r = run(tiny("rmvmul.b40"))
    assert not r["correct"]
    assert r["checks"]["missing"]["value"] > 0


def test_swap_in_that_reads_zeros():
    from repro.core.storage import RamStorage

    def make(orig):
        def read(st, page_id, out):
            out[...] = 0
        return read
    with patched(RamStorage, "read", make):
        r = run(tiny("nmatmul.b40"))
    assert not r["correct"]
    assert not r["checks"]["max_err"]["value"] <= 1.0


def test_outputs_served_from_a_cache_of_the_first_job():
    from repro.api import Session
    first = {}

    def make(orig):
        def execute(sess, *a, **kw):
            out = orig(sess, *a, **kw)
            if not first:
                first.update(out)
            return dict(first)
        return execute
    with patched(Session, "execute", make):
        r = run(tiny("nmatmul.b40"), seconds=2.5)
    assert r["attempted"] >= 2
    assert not r["correct"]


def test_engine_holding_more_frames_than_the_budget():
    from repro.core.engine import Engine

    def make(orig):
        def init(eng, program, *a, **kw):
            orig(eng, program, *a, **kw)
            extra = np.zeros((100 * program.page_slots, eng.memory.shape[1]),
                             eng.memory.dtype)
            eng.memory = np.concatenate([eng.memory, extra])
        return init
    with patched(Engine, "__init__", make):
        r = run(tiny("nmatmul.b40"))
    assert not r["correct"]
    assert r["checks"]["frames_over"]["value"] > 0


def test_resident_mix_that_swaps():
    r = run(tiny("nmatmul.resident", plan_mode="memory", memory_budget=0.4))
    assert not r["correct"]
    assert r["checks"]["swap_bytes"]["value"] > 0


def test_traced_run_reports_per_layer_metrics():
    r = run(tiny("nmatmul.b40"), trace=True)
    assert r["correct"], r["checks"]
    m = r["metrics"]
    for name in ("daemon.overhead_ms", "planner.swap_ins",
                 "batching.batched_share", "engine.host_ckks_share",
                 "storage.swap_wait_ms", "device.idle_share"):
        assert name in m, name
    # the CPU runs no NTT kernel: its readers find nothing and stay silent
    assert "ntt.launches" not in m and "ntt_roofline" not in m
    assert m["planner.swap_ins"]["value"] > 0
    assert r["device"]["window_s"] > 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
