"""Bytes per job between host and device round the NTT kernel's launches
(the ``ntt.h2d_bytes`` and ``ntt.d2h_bytes`` counters), in MB of 1e6
bytes, over the jobs whose ``daemon.job`` span the program recorded."""

import recording

COUNTERS = ("ntt.h2d_bytes", "ntt.d2h_bytes")


def read(ctx):
    rec = recording.records()
    if rec is None:
        return None
    jobs = recording.jobs(rec)
    moved = sum(n for (job, name), n in rec.counts.items()
                if name in COUNTERS and job in jobs)
    if not jobs or not moved:
        return None
    return moved / 1e6 / len(jobs)
