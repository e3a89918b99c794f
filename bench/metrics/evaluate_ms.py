"""Scorer host path (`kernels/costmodel.py` `FusedJaxScorer.metrics`:
LUT coding, copies, the device call, host `area_many`): the
`evaluate_batch` spans, per traced study."""

from bench import spans


def read(ctx):
    found = spans.named(ctx.spans, "evaluate_batch")
    if not found:
        return None
    return spans.total_us(found) / 1e3 / ctx.studies
