"""The program's own spans and counters, off unless switched on.

Each layer marks its work where it happens: ``span(name)`` round a stage,
``count(name, n)`` for what it moved.  The daemon puts a job's id in force
with ``job(job_id)``; every span and counter recorded under it carries the
id, on the engine threads too (``core.workers.run_engines`` starts each in
a copy of the caller's context).

It is on while ``enable()`` holds it on, or while a JAX profiler trace is
being recorded: each ``job`` looks at the profiler as it starts, so a
trace started between jobs is recorded from the next job on, and a
recording that starts begins empty.  Off, as it is by default, a span is
one shared no-op context manager and a counter returns at once: no clock
is read, no name is built, nothing is allocated.  Names that depend on an
op come from tables built once per op, never from strings formatted in a
loop.

On, each span is also written as a ``jax.profiler.TraceAnnotation``, so a
profiler trace holds it on its own clock beside the device's operations,
and is kept in memory as a :class:`Span`.  Job metadata stays out of the
annotation's name, which the trace's reduction groups by.  Counters add up
per (job, name).  Nothing is written anywhere: ``records`` hands over a
copy of what is held, ``drain`` hands it over and clears it.

Names follow the layers: ``daemon.*`` for the serve daemon's stages,
``engine.run``, ``<driver>.<OP>`` for a scalar protocol call,
``batched.<OP>`` for a batched group, ``storage.<OP>`` for a swap directive
and ``storage.wait`` for the part of it that blocks, ``ntt.*`` for the NTT
kernel's launches and the bytes they move.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import threading
import time
from typing import NamedTuple


class Span(NamedTuple):
    name: str
    job: int | None
    #: name of the span open round this one when it opened, if any
    parent: str | None
    thread: int
    t0_ns: int
    t1_ns: int


@dataclasses.dataclass
class Records:
    spans: list[Span]
    #: (job, name) -> total counted
    counts: dict[tuple[int | None, str], int]


_on = False
_held = False               # switched on by enable()
_profiler = None            # jax.profiler, looked up when first needed
_spans: list[tuple] = []    # Span fields; made Spans by drain()
_counts: dict[tuple[int | None, str], int] = {}
_lock = threading.Lock()
_job: contextvars.ContextVar[int | None] = contextvars.ContextVar(
    "repro_obs_job", default=None)
_parent: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "repro_obs_parent", default=None)
_OFF = contextlib.nullcontext()


def _jax_profiler():
    global _profiler
    if _profiler is None:
        from jax import profiler
        _profiler = profiler
    return _profiler


def _switch(on: bool) -> None:
    global _on
    if on and not _on:
        _jax_profiler()
        drain()                 # a recording starts empty
    _on = on


def enable() -> None:
    global _held
    _held = True
    _switch(True)


def disable() -> None:
    global _held
    _held = False
    _switch(False)


def _follow_profiler() -> None:
    """On while held on or while a profiler trace is being recorded."""
    _switch(_held or _jax_profiler().TraceAnnotation.is_enabled())


def enabled() -> bool:
    return _on


class Stage:
    """A timed stage: ``seconds`` once it has closed.  While the recorder
    is on it is a span as well, kept with the same two clock readings."""

    __slots__ = ("name", "t0_ns", "t1_ns", "_ann", "_tok")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> "Stage":
        self._ann = None
        if _on:
            self._ann = _profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
            self._tok = _parent.set(self.name)
        self.t0_ns = time.time_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.t1_ns = time.time_ns()
        if self._ann is not None:
            _parent.reset(self._tok)
            self._ann.__exit__(*exc)
            _spans.append((self.name, _job.get(), _parent.get(),
                           threading.get_ident(), self.t0_ns, self.t1_ns))

    @property
    def seconds(self) -> float:
        return (self.t1_ns - self.t0_ns) * 1e-9

    def elapsed(self) -> float:
        """Seconds since the stage opened, while it is still open."""
        return (time.time_ns() - self.t0_ns) * 1e-9


def span(name: str):
    """A span round the ``with`` block while the recorder is on."""
    return Stage(name) if _on else _OFF


def timed(name: str) -> Stage:
    """A stage its caller times whether the recorder is on or not."""
    return Stage(name)


def count(name: str, n: int) -> None:
    if not _on:
        return
    key = (_job.get(), name)
    with _lock:
        _counts[key] = _counts.get(key, 0) + n


@contextlib.contextmanager
def job(job_id: int):
    """Attribute what is recorded inside the block to ``job_id``; the
    recorder is on for the block if it is held on or the profiler is
    recording as it starts."""
    _follow_profiler()
    tok = _job.set(job_id)
    try:
        yield
    finally:
        _job.reset(tok)


def records() -> Records:
    """The records so far; the recorder keeps them."""
    with _lock:
        spans, counts = list(_spans), dict(_counts)
    return Records([Span._make(s) for s in spans], counts)


def drain() -> Records:
    """The records so far; the recorder keeps none of them."""
    global _spans, _counts
    with _lock:
        spans, counts = _spans, _counts
        _spans, _counts = [], {}
    return Records([Span._make(s) for s in spans], counts)
