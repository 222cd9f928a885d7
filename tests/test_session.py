"""End-to-end tests for the JobSpec/Session facade and the `python -m
repro` CLI: staged trace→plan→execute against workload oracles (GC and
CKKS, streaming plans, memmap storage, multi-worker), plan/run round-trip
through on-disk artifacts with spec-hash validation, process-parallel
planning, and the engine's exception-safe I/O teardown."""

import json
import os

import numpy as np
import pytest

from repro.__main__ import main
from repro.api import (JobSpec, Session, SpecMismatchError, run_job,
                       resolve_plan_config)
from repro.core import Engine, PlanConfig, ProgramFile
from repro.core.bytecode import Instr, Op, Program
from repro.core.engine import ProtocolDriver
from repro.core.storage import MemmapStorage
from repro.core.workers import plan_workers
from repro.workloads import get
from repro.workloads.runner import check_against_oracle


# ---------------------------------------------------------------------------
# Session: staged end-to-end runs against the oracles
# ---------------------------------------------------------------------------


def test_session_gc_streaming_memmap_multiworker(tmp_path):
    spec = JobSpec(workload="merge", n=256, num_workers=2, memory_budget=12,
                   lookahead=50, prefetch_pages=3, plan_mode="streaming",
                   storage="memmap", workdir=str(tmp_path))
    with Session(spec) as s:
        planned = s.plan()
        assert all(isinstance(p, ProgramFile) for p in planned)
        outs = s.execute(check=True)
    check_against_oracle(get("merge"), 256, outs)


def test_session_ckks_streaming_memmap_multiworker():
    spec = JobSpec(workload="rsum", n=32, num_workers=2, memory_budget=8,
                   lookahead=50, prefetch_pages=2, plan_mode="streaming",
                   storage="memmap")
    with Session(spec) as s:
        outs = s.execute(check=True)
    check_against_oracle(get("rsum"), 32, outs)


def test_session_real_two_party():
    outs = run_job(JobSpec(workload="merge", n=64, plan_mode="unbounded"),
                   real=True)
    check_against_oracle(get("merge"), 64, outs)


def test_session_streaming_identical_to_memory_plan():
    """The acceptance criterion: same spec, streaming vs in-memory plan,
    instruction-identical memory programs with the spec hash stamped."""
    kw = dict(workload="sort", n=128, memory_budget=10, lookahead=40,
              prefetch_pages=2)
    with Session(JobSpec(**kw)) as a, \
            Session(JobSpec(plan_mode="streaming", **kw)) as b:
        mem = a.plan()
        memf = b.plan()
        assert list(memf[0].iter_instrs()) == mem[0].instrs
        h = JobSpec(**kw).plan_hash()
        assert mem[0].meta["spec_hash"] == h
        assert memf[0].meta["spec_hash"] == h


def test_fractional_budget_resolution():
    spec = JobSpec(workload="merge", n=1024, memory_budget=0.25,
                   lookahead=100, prefetch_pages=8)
    with Session(spec) as s:
        cfg = resolve_plan_config(spec, s.trace()[0], s.working_set(0))
        ws = s.working_set(0)
        assert 8 + 8 <= cfg.num_frames < ws
        assert cfg.prefetch_pages <= max(cfg.num_frames // 4, 1)
        outs = s.execute(check=True)
        assert outs


def test_simulate_scenarios():
    spec = JobSpec(workload="merge", n=512, memory_budget=0.3,
                   lookahead=100, prefetch_pages=8, track_plan_memory=True)
    from repro.scenarios import OS_PAGE_BYTES, STORAGE, cost_fn
    with Session(spec) as s:
        (sc,) = s.simulate(cost_fn("gc"), model=STORAGE,
                           os_page_bytes=OS_PAGE_BYTES)
    assert sc.unbounded.total > 0
    assert sc.os.total >= sc.unbounded.total
    assert sc.mage.total >= sc.unbounded.total
    assert sc.report.peak_mem_bytes > 0
    assert sc.working_set_pages > sc.config.num_frames


def test_spec_validation():
    with pytest.raises(ValueError, match="plan_mode"):
        JobSpec(workload="merge", plan_mode="bogus")
    with pytest.raises(ValueError, match="memory_budget"):
        JobSpec(workload="merge", plan_mode="memory")
    with pytest.raises(ValueError, match="no memory_budget"):
        JobSpec(workload="merge", plan_mode="unbounded", memory_budget=8)
    with pytest.raises(ValueError, match="fractional"):
        JobSpec(workload="merge", memory_budget=1.5)
    with pytest.raises(KeyError):
        run_job(JobSpec(workload="merge", n=32, plan_mode="unbounded",
                        driver="no-such-driver"))


def test_plan_hash_covers_plan_fields_only():
    a = JobSpec(workload="merge", n=128, memory_budget=10)
    assert a.plan_hash() == JobSpec(workload="merge", n=128, memory_budget=10,
                                    storage="memmap", parallel_plan="thread",
                                    plan_mode="streaming").plan_hash()
    assert a.plan_hash() != JobSpec(workload="merge", n=256,
                                    memory_budget=10).plan_hash()
    assert a.plan_hash() != JobSpec(workload="merge", n=128,
                                    memory_budget=12).plan_hash()
    # n=None resolves to the workload default before hashing
    w = get("merge")
    assert JobSpec(workload="merge", memory_budget=10).plan_hash() == \
        JobSpec(workload="merge", n=w.default_n, memory_budget=10).plan_hash()


# ---------------------------------------------------------------------------
# plan artifacts + CLI round-trip
# ---------------------------------------------------------------------------


def test_save_plan_then_from_plan(tmp_path):
    spec = JobSpec(workload="merge", n=128, num_workers=2, memory_budget=10,
                   lookahead=40, prefetch_pages=2, plan_mode="streaming")
    with Session(spec) as s:
        s.save_plan(tmp_path)
    sess = Session.from_plan(tmp_path, storage="memmap")
    with sess:
        outs = sess.execute(check=True)
    check_against_oracle(get("merge"), 128, outs)


def test_cli_plan_run_roundtrip_and_tamper_rejection(tmp_path, capsys):
    job = tmp_path / "job"
    assert main(["plan", "--workload", "merge", "-n", "128", "--workers",
                 "2", "--budget", "10", "--lookahead", "40", "--prefetch",
                 "2", "--out", str(job)]) == 0
    assert (job / "job.json").exists()
    assert (job / "worker0.memory.bc").exists()
    assert main(["run", str(job), "--check"]) == 0
    assert "oracle check OK" in capsys.readouterr().out

    # tampering with the spec after planning must be rejected
    manifest = json.loads((job / "job.json").read_text())
    manifest["spec"]["n"] = 64
    (job / "job.json").write_text(json.dumps(manifest))
    with pytest.raises(SystemExit) as ei:
        main(["run", str(job), "--check"])
    assert ei.value.code == 2


def test_cli_fabric_refuses_two_tpu_ranks(tmp_path, monkeypatch):
    # every batched rank would claim the one TPU: fail before spawning
    import repro.__main__ as cli
    job = tmp_path / "job"
    assert main(["plan", "--workload", "merge", "-n", "64", "--workers",
                 "2", "--budget", "10", "--exec-backend", "batched",
                 "--out", str(job)]) == 0
    monkeypatch.setattr(cli, "_tpu_visible", lambda env: True)
    monkeypatch.setattr(cli.subprocess, "Popen", None)   # must not spawn
    with pytest.raises(SystemExit, match="claim the TPU"):
        main(["fabric", str(job)])


@pytest.mark.parametrize("platforms,visible", [("cpu", False),
                                               ("tpu,cpu", None)])
def test_tpu_visible_follows_jax_platforms(platforms, visible):
    import importlib.util
    from repro.__main__ import _tpu_visible
    if visible is None:
        visible = importlib.util.find_spec("libtpu") is not None
    assert _tpu_visible({"JAX_PLATFORMS": platforms}) is visible


def test_from_plan_rejects_foreign_program_file(tmp_path):
    a, b = tmp_path / "a", tmp_path / "b"
    for d, n in ((a, 128), (b, 64)):
        with Session(JobSpec(workload="merge", n=n, memory_budget=10,
                             lookahead=40, prefetch_pages=2,
                             plan_mode="streaming")) as s:
            s.save_plan(d)
    # swap a's program file for b's: stamped hash disagrees with job.json
    os.replace(b / "worker0.memory.bc", a / "worker0.memory.bc")
    with pytest.raises(SpecMismatchError, match="artifact and spec"):
        Session.from_plan(a)


def test_cli_bench_tiny_json(tmp_path):
    out = tmp_path / "bench.json"
    assert main(["bench", "--tiny", "--cases", "rsum=64",
                 "--json", str(out)]) == 0
    doc = json.loads(out.read_text())
    assert doc["schema_version"] == 1
    rows = doc["rows"]
    assert rows[0]["workload"] == "rsum"
    assert {"unbounded_s", "os_s", "mage_s", "plan_peak_mb",
            "program_bytes"} <= set(rows[0])
    # --tiny adds a streaming case through the file pipeline
    assert rows[-1]["plan_mode"] == "streaming"


# ---------------------------------------------------------------------------
# process-parallel planning (satellite: dodge the GIL)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("streaming", [False, True])
def test_plan_workers_process_mode(tmp_path, streaming):
    progs = get("merge").trace(128, 2)
    cfg = PlanConfig(num_frames=10, lookahead=40, prefetch_pages=2)
    ser, ser_rep = plan_workers(progs, cfg)
    par, par_rep = plan_workers(progs, cfg, parallel="process",
                                streaming=streaming,
                                workdir=str(tmp_path) if streaming else None)
    for a, b in zip(ser, par):
        got = list(b.iter_instrs()) if streaming else b.instrs
        assert got == a.instrs
    assert [r.replacement for r in ser_rep] == \
        [r.replacement for r in par_rep]


def test_plan_workers_per_worker_configs():
    progs = get("merge").trace(128, 2)
    cfgs = [PlanConfig(num_frames=10, lookahead=40, prefetch_pages=2),
            PlanConfig(num_frames=14, lookahead=40, prefetch_pages=2)]
    planned, _ = plan_workers(progs, cfgs)
    # memory programs carry replacement frames = budget - prefetch buffer
    assert planned[0].num_frames == cfgs[0].replacement_frames == 8
    assert planned[1].num_frames == cfgs[1].replacement_frames == 12
    with pytest.raises(ValueError, match="configs"):
        plan_workers(progs, cfgs[:1])


# ---------------------------------------------------------------------------
# engine teardown (satellite: no leaked AsyncIO threads / open storage)
# ---------------------------------------------------------------------------


class _BoomDriver(ProtocolDriver):
    lane = 1
    dtype = np.uint64

    def execute(self, op, imm, outs, ins):
        raise RuntimeError("boom")


def test_engine_closes_io_on_driver_error():
    prog = Program(instrs=[Instr(Op.INPUT, outs=((0, 4),), imm=(4, 1, 0, 0))],
                   page_shift=2, protocol="gc", vspace_slots=4)
    storage = MemmapStorage((4, 1), np.uint64)
    swap_path = storage.path
    eng = Engine(prog, _BoomDriver(), storage=storage)
    with pytest.raises(RuntimeError, match="boom"):
        eng.run()
    # storage closed (temp swap file unlinked) and I/O pool shut down
    assert not os.path.exists(swap_path)
    assert eng.io.pool._shutdown
