"""Share of the daemon's execute time spent inside scalar ``CkksDriver``
calls (the ``ckks.*`` spans the benchmark wraps round them)."""


def read(ctx):
    execute = sum(j["timings"]["execute_s"] for j in ctx.jobs)
    inside = sum(b - a for name, a, b in ctx.spans
                 if name.startswith("ckks."))
    if not execute or not inside:
        return None
    return 100.0 * inside * 1e-9 / execute
