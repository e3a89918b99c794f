"""XLA programs (one per app and pool bucket, built by every study):
tracing, lowering, and compiling or loading from the persistent cache,
from JAX's own monitoring events, per traced study."""


def read(ctx):
    if not ctx.compile_events:
        return None
    return sum(e[2] for e in ctx.compile_events) * 1e3 / ctx.studies
