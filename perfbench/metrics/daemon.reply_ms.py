"""Mean per job of the daemon's reply: its ``daemon.encode`` span (digest,
outputs as lists) and ``daemon.send`` span (JSON, socket), over the jobs
whose ``daemon.job`` span the program recorded."""

import recording

REPLY = ("daemon.encode", "daemon.send")


def read(ctx):
    rec = recording.records()
    if rec is None:
        return None
    jobs = recording.jobs(rec)
    if not jobs:
        return None
    inside = sum(s.t1_ns - s.t0_ns for s in rec.spans
                 if s.name in REPLY and s.job in jobs)
    return inside * 1e-6 / len(jobs)
