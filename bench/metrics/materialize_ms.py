"""Study search phase outside rounds: turning the evaluated rows into
configurations and back, per traced study.  The `search.materialize`
spans (`run_search` after its loop), `search.export` (the worker record
in `_search_app_task`: the evaluated log as a `ConfigBatch`, the cache
export, the stats) and `study.rebuild` (`Study._rebuild_result`: the
cache merge and the configurations again)."""

from bench import spans

NAMES = ("search.materialize", "search.export", "study.rebuild")


def read(ctx):
    found = [s for name in NAMES for s in spans.named(ctx.spans, name)]
    if not found:
        return None
    return spans.total_us(found) / 1e3 / ctx.studies
