"""XLA programs per app and bucket: programs built (compiled or loaded
from the persistent cache) per traced study, from the `scorer.programs`
counter."""


def read(ctx):
    programs = ctx.counters.get("scorer.programs")
    if programs is None:
        return None
    return programs / ctx.studies
