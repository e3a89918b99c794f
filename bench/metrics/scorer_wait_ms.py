"""Scorer host path (`FusedJaxScorer.metrics`): the device call as the
host waits on it, dispatch, the host-to-device copy, the kernel and the
readback (`scorer.run` spans), per traced study."""

from bench import spans


def read(ctx):
    found = spans.named(ctx.spans, "scorer.run")
    if not found:
        return None
    return spans.total_us(found) / 1e3 / ctx.studies
