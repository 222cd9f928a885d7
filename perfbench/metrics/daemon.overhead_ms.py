"""Mean per job of the daemon's own time outside execution: its
``total_s`` less its ``execute_s`` (session, cache reads, plan load)."""


def read(ctx):
    t = [j["timings"] for j in ctx.jobs]
    if not t:
        return None
    return sum(x["total_s"] - x["execute_s"] for x in t) / len(t) * 1e3
