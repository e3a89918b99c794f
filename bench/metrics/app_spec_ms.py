"""Study construction (`dse/study.py` `Study.__init__`): building each
app's `AppSpec` (the graph's Fig. 5 liveness walk and op stream, on a
memoized trace), the `study.app_specs` spans, per traced study.  None on
a program without the span."""

from bench import spans


def read(ctx):
    found = spans.named(ctx.spans, "study.app_specs")
    if not found:
        return None
    return spans.total_us(found) / 1e3 / ctx.studies
