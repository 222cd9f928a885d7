"""What the program recorded itself in a traced run: its own spans and
counters (``repro.obs``), which it keeps while the profiler records, that
is through the traced window.  A reader of them asks ``records()``, which
gives None where the program has no recorder."""

from __future__ import annotations


def records():
    """The program's ``repro.obs.Records`` so far, or None."""
    try:
        from repro import obs
    except ImportError:         # a program without its own recorder
        return None
    return obs.records()


def jobs(rec) -> set:
    """The jobs whose ``daemon.job`` span closed in the recording."""
    return {s.job for s in rec.spans if s.name == "daemon.job"}
