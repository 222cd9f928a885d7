"""Share of executed instructions that ran in batched groups
(``EngineStats.batched_instructions`` over ``instructions``)."""


def read(ctx):
    stats = [s for e in ctx.executes for s in e["stats"]]
    total = sum(s.instructions for s in stats)
    if not total:
        return None
    return 100.0 * sum(s.batched_instructions for s in stats) / total
