"""Batched peak repair on the host (`core/space.py`
`DesignSpace.repair_for_peaks_many`): the `space.repair` spans, per traced
study.  They lie inside `round.propose`, so `engine_host_ms` counts them
too."""

from bench import spans


def read(ctx):
    found = spans.named(ctx.spans, "space.repair")
    if not found:
        return None
    return spans.total_us(found) / 1e3 / ctx.studies
