"""Swap-ins per job of the executed plan (``PlanReport.replacement``);
nothing to read where the plan is unbounded."""


def read(ctx):
    got = [r.replacement.swap_ins for e in ctx.executes
           for r in e["reports"] if r.replacement is not None]
    if not got or len(got) != len(ctx.executes):
        return None
    return sum(got) / len(got)
