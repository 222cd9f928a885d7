"""Time per job the engine blocked on its storage: the ``storage.wait``
spans (a synchronous swap's I/O, or a prefetch slot whose transfer had not
finished), over the jobs whose ``daemon.job`` span the program recorded."""

import recording


def read(ctx):
    rec = recording.records()
    if rec is None:
        return None
    jobs = recording.jobs(rec)
    if not jobs:
        return None
    inside = sum(s.t1_ns - s.t0_ns for s in rec.spans
                 if s.name == "storage.wait" and s.job in jobs)
    return inside * 1e-6 / len(jobs)
