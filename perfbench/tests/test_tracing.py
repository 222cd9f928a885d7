"""The reduction from a trace to busy time, idle gaps and kernel time,
and the bytes function of the NTT kernel.

``data/ntt_small.xplane.pb`` was recorded on a TPU v5 lite: one client
span holding a daemon span, a host-only ``ckks.INPUT`` span (3 ms), one
forward and one inverse NTT launch of a (2, 4096) batch (padded to 8)
inside ``batched.CT_MUL_NR``, and a host-only ``storage.SWAP_IN`` span.
"""

import os

import pytest

import cell
import kernels
import tracing
from conftest import HERE

SMALL = os.path.join(HERE, "data", "ntt_small.xplane.pb")


def test_ntt_bytes_hand_counted():
    # (8, 4096) uint32 in and out, 12 stages x 4096 uint32 twiddles
    assert kernels.ntt_bytes(8, 4096) == 8 * 4096 * 4 * 2 + 12 * 4096 * 4
    assert kernels.ntt_bytes(8, 4096) == 458752
    assert kernels.ntt_bytes(64, 4096) == 2293760
    assert kernels.ntt_bytes(8, 128) == 8 * 128 * 8 + 7 * 128 * 4
    peaks = {"hbm_bytes_per_s": 819e9}
    assert kernels.least_seconds(819, peaks) == pytest.approx(1e-9)


def test_merge_and_cover():
    m = tracing.merge([(5, 9), (0, 2), (1, 3), (9, 10), (12, 12)])
    assert m.tolist() == [[0, 3], [5, 10]]
    cov = tracing._Covered(m)
    assert cov.within(0, 20).item() == 8
    assert cov.within(2, 6).item() == 2
    assert cov.within(3, 5).item() == 0
    assert cov.within(-4, 1).item() == 1
    empty = tracing._Covered(tracing.merge([]))
    assert empty.within(0, 5).item() == 0


def test_leaves_split_nested_spans_into_self_time():
    spans = [("a", 0, 100), ("b", 10, 40), ("c", 20, 30), ("d", 50, 60)]
    got = tracing.leaves(spans)
    assert got == [("a", 0, 10), ("b", 10, 20), ("c", 20, 30),
                   ("b", 30, 40), ("a", 40, 50), ("d", 50, 60),
                   ("a", 60, 100)]
    assert sum(b - a for _, a, b in got) == 100


def synthetic() -> tracing.Trace:
    dev = [("fusion", 100, 200), ("ntt_kernel", 300, 500),
           ("ntt_kernel", 450, 600), ("copy", 950, 1200)]
    host = {"h/0/main": [("client.submit", 0, 1000)],
            "h/1/daemon": [("daemon.submit", 50, 900),
                           ("ckks.INPUT", 60, 290),
                           ("storage.SWAP_IN", 600, 700)]}
    return tracing.Trace({"/device:TPU:0": dev}, host)


def test_reduction_on_a_synthetic_trace():
    red = tracing.Reduction(synthetic())
    assert (red.lo, red.hi) == (0, 1000)
    assert red.window_s == pytest.approx(1000e-9)
    # busy: [100,200] + [300,600] + [950,1000] clipped to the window
    assert red.busy_s == pytest.approx(450e-9)
    assert red.op_seconds("ntt") == pytest.approx(350e-9)
    assert red.top_ops(2) == [["ntt_kernel", pytest.approx(350e-9)],
                              ["fusion", pytest.approx(100e-9)]]
    gaps = dict((k, v * 1e9) for k, v in red.idle_gaps())
    # idle = 550 ns: [0,100] [200,300] [600,950]
    assert gaps["ckks.INPUT"] == pytest.approx(40 + 90)     # [60,100]+[200,290]
    assert gaps["storage.SWAP_IN"] == pytest.approx(100)    # [600,700]
    # [50,60] + [290,300] + [700,900]
    assert gaps["daemon.submit"] == pytest.approx(10 + 10 + 200)
    assert gaps["client.submit"] == pytest.approx(50 + 50)   # [0,50]+[900,950]
    assert sum(gaps.values()) == pytest.approx(550)


def test_reduction_on_a_recorded_tpu_trace():
    import re
    from harness import Context
    trace = tracing.load(SMALL)
    assert list(trace.devices) == ["/device:TPU:0"]
    spans = {n: (a, b) for s in trace.host.values() for n, a, b in s}
    assert {"client.submit", "daemon.submit", "ckks.INPUT",
            "batched.CT_MUL_NR", "storage.SWAP_IN"} <= set(spans)
    red = tracing.Reduction(trace)
    assert (red.lo, red.hi) == spans["client.submit"]
    assert 0 < red.busy_s < red.window_s
    gaps = dict(red.idle_gaps())
    # the host-only spans leave the device idle nearly all through
    for name in ("ckks.INPUT", "storage.SWAP_IN"):
        a, b = spans[name]
        assert 0.8 * (b - a) * 1e-9 <= gaps[name] <= (b - a) * 1e-9
    assert sum(gaps.values()) == pytest.approx(red.window_s - red.busy_s,
                                               rel=1e-6)
    # the roofline reader finds the two launches' kernel and nothing else
    read = cell.reader("ntt_roofline")
    pattern = read.__globals__["PATTERN"]
    kernel = [n for n, _, _ in trace.devices["/device:TPU:0"]
              if re.search(pattern, n)]
    assert len(kernel) == 2
    ctx = Context([], [], [], [(8, 4096), (8, 4096)], red,
                  {"hbm_bytes_per_s": 819e9})
    share = read(ctx)
    assert 0 < share <= 100
    assert share == pytest.approx(
        100 * 2 * kernels.ntt_bytes(8, 4096) / 819e9
        / red.op_seconds(pattern))
    top = red.top_ops(3)
    assert all(" = " in name and len(name) < 80 for name, _ in top)
