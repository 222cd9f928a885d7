"""Host time per job in the engine's swap directives (``SWAP_*``,
``ISSUE_*``, ``FINISH_*``, ``COPY_OUT``: the ``storage.*`` spans)."""


def read(ctx):
    inside = sum(b - a for name, a, b in ctx.spans
                 if name.startswith("storage."))
    if not inside or not ctx.jobs:
        return None
    return inside * 1e-6 / len(ctx.jobs)
