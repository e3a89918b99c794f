"""Scorer host path (`FusedJaxScorer.metrics`): LUT coding and padding
of the pool (`scorer.code` spans) and the host area polynomial
(`scorer.area`), per traced study."""

from bench import spans


def read(ctx):
    found = (spans.named(ctx.spans, "scorer.code")
             + spans.named(ctx.spans, "scorer.area"))
    if not found:
        return None
    return spans.total_us(found) / 1e3 / ctx.studies
