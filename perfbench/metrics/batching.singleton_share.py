"""Share of the instructions the driver could batch that ran one by one:
``EngineStats.batchable_scalar`` over it plus ``batched_instructions``."""


def read(ctx):
    stats = [s for e in ctx.executes for s in e["stats"]]
    if not stats or not all(hasattr(s, "batchable_scalar") for s in stats):
        return None
    alone = sum(s.batchable_scalar for s in stats)
    batchable = alone + sum(s.batched_instructions for s in stats)
    if not batchable:
        return None
    return 100.0 * alone / batchable
