"""Study synthesis (`dse/study.py`): the `phase.synthesize` spans, per
traced study."""

from bench import spans


def read(ctx):
    found = spans.named(ctx.spans, "phase.synthesize")
    if not found:
        return None
    return spans.total_us(found) / 1e3 / ctx.studies
