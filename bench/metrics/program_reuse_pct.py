"""XLA programs per app and bucket: the share of program lookups in
`FusedJaxScorer.metrics` that found a compiled program of the same
shapes (counter `scorer.program_reuses`) among all lookups (those plus
`scorer.programs`, the ones that compiled), in the traced window."""


def read(ctx):
    reuses = ctx.counters.get("scorer.program_reuses")
    if reuses is None:
        return None
    return 100.0 * reuses / (reuses + ctx.counters.get("scorer.programs", 0))
