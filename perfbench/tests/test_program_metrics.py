"""The readers of the program's own spans and counters, on a synthetic
recording and on a traced run at the CPU's tiny size, and silent where the
program has no recorder."""

import sys
import types

import pytest

import cell
import recording
from harness import Context
from repro.obs import Records, Span
from test_faults import run, tiny

NEW = ("daemon.reply_ms", "engine.scalar_share", "batching.singleton_share",
       "storage.blocked_ms", "ntt.transfer_mb")


def span(name, job, t0, t1, parent=None, thread=1):
    return Span(name, job, parent, thread, t0, t1)


def synthetic() -> Records:
    """Two jobs, 10 ms apart; job 3's reply is still being sent (no
    ``daemon.job`` span yet), so it counts for nothing."""
    spans = []
    for job, base in ((1, 0), (2, 10_000_000)):
        spans += [
            span("engine.run", job, base + 1_000_000, base + 5_000_000,
                 "daemon.execute", thread=2),
            span("ckks.CT_MUL_NR", job, base + 1_000_000, base + 3_000_000,
                 "engine.run", thread=2),
            span("ckks.OUTPUT", job, base + 3_000_000, base + 3_500_000,
                 "engine.run", thread=2),
            span("batched.CT_MUL_NR", job, base + 3_500_000,
                 base + 4_000_000, "engine.run", thread=2),
            span("ntt.forward", job, base + 3_600_000, base + 3_700_000,
                 "batched.CT_MUL_NR", thread=2),
            span("storage.FINISH_SWAP_IN", job, base + 4_000_000,
                 base + 4_500_000, "engine.run", thread=2),
            span("storage.wait", job, base + 4_100_000, base + 4_350_000,
                 "storage.FINISH_SWAP_IN", thread=2),
            span("daemon.encode", job, base + 5_000_000, base + 6_000_000,
                 "daemon.job"),
            span("daemon.send", job, base + 6_000_000, base + 8_000_000,
                 "daemon.job"),
            span("daemon.job", job, base, base + 8_000_000),
        ]
    spans += [span("daemon.encode", 3, 20_000_000, 29_000_000, "daemon.job")]
    counts = {(1, "ntt.launches"): 2, (1, "ntt.h2d_bytes"): 294_912,
              (1, "ntt.d2h_bytes"): 262_144, (2, "ntt.launches"): 2,
              (2, "ntt.h2d_bytes"): 294_912, (2, "ntt.d2h_bytes"): 262_144,
              (3, "ntt.h2d_bytes"): 10**9}
    return Records(spans, counts)


def stats(alone, batched):
    return types.SimpleNamespace(batchable_scalar=alone,
                                 batched_instructions=batched,
                                 instructions=alone + batched + 100)


@pytest.fixture()
def program(monkeypatch):
    """Installs what ``recording.records`` gives the readers."""
    def install(rec):
        monkeypatch.setattr(recording, "records", lambda: rec)
    return install


def ctx(executes=()):
    return Context([], list(executes), [], [], None, {})


def test_readers_on_a_synthetic_program(program):
    program(synthetic())
    c = ctx([{"stats": [stats(30, 10), stats(6, 2)]}])
    read = {name: cell.reader(name) for name in NEW}
    # encode 1 ms + send 2 ms a job
    assert read["daemon.reply_ms"](c) == pytest.approx(3.0)
    # (2 + 0.5) ms of driver calls in 4 ms of engine.run, each job
    assert read["engine.scalar_share"](c) == pytest.approx(62.5)
    assert read["batching.singleton_share"](c) == pytest.approx(75.0)
    assert read["storage.blocked_ms"](c) == pytest.approx(0.25)
    assert read["ntt.transfer_mb"](c) == pytest.approx(0.557056)


def test_storage_blocked_reads_zero_where_nothing_waited(program):
    rec = synthetic()
    rec.spans[:] = [s for s in rec.spans if s.name != "storage.wait"]
    program(rec)
    assert cell.reader("storage.blocked_ms")(ctx()) == 0.0


@pytest.mark.parametrize("name", NEW)
def test_silent_without_the_program_recorder(name, program):
    # a program that has neither the recorder nor the engine's counter
    program(None)
    old = types.SimpleNamespace(batched_instructions=10, instructions=100)
    assert cell.reader(name)(ctx([{"stats": [old]}])) is None


@pytest.mark.parametrize("name", ["daemon.reply_ms", "engine.scalar_share",
                                  "storage.blocked_ms", "ntt.transfer_mb"])
def test_silent_on_an_empty_recording(name, program):
    program(Records([], {}))
    assert cell.reader(name)(ctx()) is None


def test_records_none_where_the_program_has_no_recorder(monkeypatch):
    import repro
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    monkeypatch.delattr(repro, "obs", raising=False)
    assert recording.records() is None


def test_traced_run_reports_the_program_metrics():
    # two jobs or more: the last job's reply span can close after the
    # trace has stopped, so only an earlier job's shows for certain
    r = run(tiny("nmatmul.b40"), seconds=4.0, trace=True)
    assert r["attempted"] >= 2
    assert r["correct"], r["checks"]
    m = r["metrics"]
    for name in ("daemon.reply_ms", "engine.scalar_share",
                 "batching.singleton_share", "storage.blocked_ms"):
        assert name in m, name
    assert 0 < m["engine.scalar_share"]["value"] < 100
    assert 0 < m["batching.singleton_share"]["value"] <= 100
    # the CPU runs no NTT kernel: its bytes are never counted
    assert "ntt.transfer_mb" not in m
    # the reply is the program's own span in the breakdown
    assert "daemon.send" in dict(r["breakdown"]["idle_gaps"])


def test_every_new_metric_is_declared_for_its_cells():
    for name, cells in (("daemon.reply_ms", 3), ("engine.scalar_share", 3),
                        ("batching.singleton_share", 3),
                        ("storage.blocked_ms", 2), ("ntt.transfer_mb", 3)):
        got = [c for c in ("nmatmul.b40", "rmvmul.b40", "nmatmul.resident")
               if name in {m["name"] for m in cell.load(c).per_layer}]
        assert len(got) == cells, (name, got)
    assert "storage.blocked_ms" not in {
        m["name"] for m in cell.load("nmatmul.resident").per_layer}
