"""Evaluator row cache (`core/search/rowcache.py`): hits over probes,
from the study telemetry counters `evaluator.cache_hits` and
`evaluator.cache_misses` summed over the traced studies."""


def read(ctx):
    hits = ctx.counters.get("evaluator.cache_hits")
    misses = ctx.counters.get("evaluator.cache_misses")
    if hits is None or misses is None or hits + misses == 0:
        return None
    return 100.0 * hits / (hits + misses)
