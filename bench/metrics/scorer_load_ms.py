"""XLA programs per app and bucket: table uploads and program builds
(an XLA compile or a persistent-cache load) in
`FusedJaxScorer.metrics` (`scorer.program` spans), per traced study."""

from bench import spans


def read(ctx):
    found = spans.named(ctx.spans, "scorer.program")
    if not found:
        return None
    return spans.total_us(found) / 1e3 / ctx.studies
