"""NTT kernel launches per job, counted by wrapping the program's
``kernels.ntt.kernel.ntt_pallas``."""


def read(ctx):
    if not ctx.launches or not ctx.jobs:
        return None
    return len(ctx.launches) / len(ctx.jobs)
