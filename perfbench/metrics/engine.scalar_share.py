"""Share of the engine's run time spent in scalar protocol calls: the
``<driver>.<OP>`` spans (``ckks.CT_MUL_NR``, ...) over the ``engine.run``
spans the program recorded."""

import recording

#: span prefixes of the other layers; any other prefix names a driver
LAYERS = ("daemon", "engine", "batched", "storage", "ntt")


def read(ctx):
    rec = recording.records()
    if rec is None:
        return None
    run = sum(s.t1_ns - s.t0_ns for s in rec.spans if s.name == "engine.run")
    scalar = sum(s.t1_ns - s.t0_ns for s in rec.spans
                 if s.name.split(".", 1)[0] not in LAYERS)
    if not run or not scalar:
        return None
    return 100.0 * scalar / run
