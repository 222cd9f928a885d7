"""The reduction from a profiler trace to device busy time and its gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes: the device
operations of each TPU plane (its ``XLA Ops`` line), and the host spans
the benchmark wrote as ``TraceAnnotation``s.  ``Reduction`` answers on the
window the host spans bound: busy time (the union of device operations,
averaged over the chips), the time of the operations whose name matches a
pattern, the operations that took most time, and where the idle time went,
put down to the innermost host span open during it.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

#: host spans the benchmark writes; everything else on the host is JAX's
SPAN_PREFIXES = ("client.", "daemon.", "session.", "engine.", "ckks.",
                 "batched.", "storage.", "ntt.")
#: the span that covers a whole job from the client's side
CLIENT = "client.submit"


def start(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # Python calls would swamp the trace
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop(log_dir: str) -> str:
    import jax
    jax.profiler.stop_trace()
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise RuntimeError(f"no trace written under {log_dir}")
    return found[-1]


@dataclasses.dataclass
class Trace:
    #: per device plane: (name, start_ns, end_ns) of each operation
    devices: dict[str, list[tuple[str, int, int]]]
    #: per host thread: (name, start_ns, end_ns) of each benchmark span
    host: dict[str, list[tuple[str, int, int]]]


def short_name(op: str) -> str:
    """``%fusion.4 = u32[4096] fusion`` for the HLO text a TPU trace gives
    an operation (its layouts and operands dropped)."""
    lhs, _, rhs = op.partition(" = ")
    if not rhs:
        return op[:120]
    rhs = re.sub(r"\{[^{}]*\}", "", rhs)
    m = re.match(r"(\([^()]*\)|\S+) ([\w-]+)", rhs)
    return f"{lhs} = {m.group(1)} {m.group(2)}" if m else lhs


def _device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") and name[12:].isdigit()


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    host: dict[str, list] = {}
    for plane in pd.planes:
        if _device_plane(plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = [
                        (ev.name, int(ev.start_ns), int(ev.end_ns))
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                got = [(ev.name, int(ev.start_ns), int(ev.end_ns))
                       for ev in line.events
                       if ev.name.startswith(SPAN_PREFIXES)]
                if got:
                    host[f"{plane.name}/{i}/{line.name}"] = got
    return Trace(devices, host)


def merge(intervals) -> np.ndarray:
    """Sorted disjoint (k, 2) union of (start, end) intervals."""
    iv = np.asarray(sorted((int(a), int(b)) for a, b in intervals
                           if b > a), dtype=np.int64).reshape(-1, 2)
    if len(iv) == 0:
        return iv
    out = [list(iv[0])]
    for a, b in iv[1:]:
        if a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return np.asarray(out, dtype=np.int64)


class _Covered:
    """Time covered by a merged interval set before each instant."""

    def __init__(self, merged: np.ndarray):
        self.starts = merged[:, 0]
        self.ends = merged[:, 1]
        self.before = np.concatenate(
            [[0], np.cumsum(self.ends - self.starts)])

    def upto(self, t) -> np.ndarray:
        t = np.asarray(t, dtype=np.int64)
        if len(self.starts) == 0:
            return np.zeros_like(t)
        i = np.searchsorted(self.starts, t, side="right")
        prev_end = np.where(i > 0, self.ends[np.maximum(i - 1, 0)], 0)
        inside = np.where(i > 0, np.minimum(t, prev_end)
                          - self.starts[np.maximum(i - 1, 0)], 0)
        return self.before[np.maximum(i - 1, 0)] * (i > 0) + inside

    def within(self, a, b) -> np.ndarray:
        return self.upto(b) - self.upto(a)


def leaves(spans: list[tuple[str, int, int]]
           ) -> list[tuple[str, int, int]]:
    """The self-time pieces of properly nested spans on one thread, each
    labelled with the innermost span open over it."""
    events = sorted(spans, key=lambda s: (s[1], -s[2]))
    out: list[tuple[str, int, int]] = []
    stack: list[tuple[str, int, int]] = []
    cursor = None
    for name, a, b in events:
        while stack and stack[-1][2] <= a:
            top = stack.pop()
            if cursor < top[2]:
                out.append((top[0], cursor, top[2]))
            cursor = max(cursor, top[2])
        if stack and cursor < a:
            out.append((stack[-1][0], cursor, a))
        stack.append((name, a, b))
        cursor = a
    while stack:
        top = stack.pop()
        if cursor < top[2]:
            out.append((top[0], cursor, top[2]))
        cursor = max(cursor, top[2])
    return out


class Reduction:
    """Busy and idle time of the traced window that the client spans
    bound: from the first job's submit to the last job's outputs."""

    def __init__(self, trace: Trace):
        self.trace = trace
        client = [s for spans in trace.host.values() for s in spans
                  if s[0] == CLIENT]
        if not client:
            raise ValueError("the trace holds no client span")
        self.lo = min(s[1] for s in client)
        self.hi = max(s[2] for s in client)
        self.busy = {}
        for dev, ops in trace.devices.items():
            self.busy[dev] = merge((max(a, self.lo), min(b, self.hi))
                                   for _, a, b in ops)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the chips traced."""
        if not self.busy:
            return 0.0
        tot = [float((m[:, 1] - m[:, 0]).sum()) for m in self.busy.values()]
        return sum(tot) / len(tot) * 1e-9

    def op_seconds(self, pattern: str) -> float:
        """Summed device time of the operations whose name matches."""
        rx = re.compile(pattern)
        return sum(min(b, self.hi) - max(a, self.lo)
                   for ops in self.trace.devices.values()
                   for name, a, b in ops
                   if rx.search(name) and b > self.lo and a < self.hi) * 1e-9

    def top_ops(self, k: int = 10) -> list[list]:
        """The ``k`` operations that took most device time, by
        :func:`short_name`, in seconds averaged over the chips."""
        tot: dict[str, int] = {}
        for ops in self.trace.devices.values():
            for name, a, b in ops:
                d = min(b, self.hi) - max(a, self.lo)
                if d > 0:
                    name = short_name(name)
                    tot[name] = tot.get(name, 0) + d
        n = max(len(self.trace.devices), 1)
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / n * 1e-9] for name, ns in best]

    def idle_gaps(self, k: int = 10) -> list[list]:
        """Idle seconds of the first chip, summed by the innermost
        benchmark span the host had open; the job's client span counts
        only where no other span was open, and ``host.other`` where none
        was."""
        busy = next(iter(self.busy.values()), np.zeros((0, 2), np.int64))
        cov = _Covered(busy)

        def idle(a, b):
            a, b = max(a, self.lo), min(b, self.hi)
            return 0 if b <= a else int(b - a - cov.within(a, b))

        total_idle = idle(self.lo, self.hi)
        by_name: dict[str, int] = {}
        inner, outer = [], []
        for spans in self.trace.host.values():
            for name, a, b in leaves(spans):
                (outer if name == CLIENT else inner).append((a, b))
                if name != CLIENT:
                    by_name[name] = by_name.get(name, 0) + idle(a, b)
        inner_m = merge(inner)
        inner_idle = sum(idle(a, b) for a, b in inner_m)
        # client-only time: the client span minus every inner span
        outer_m = merge(outer)
        both = merge(list(map(tuple, inner_m)) + list(map(tuple, outer_m)))
        client_idle = sum(idle(a, b) for a, b in both) - inner_idle
        if client_idle > 0:
            by_name[CLIENT] = client_idle
        other = total_idle - inner_idle - max(client_idle, 0)
        if other > 0:
            by_name["host.other"] = other
        best = sorted(by_name.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns * 1e-9] for name, ns in best if ns > 0]
