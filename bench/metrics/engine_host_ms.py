"""Engine rounds on the host: the engines' `propose` and `observe`
(`round.propose`, `round.observe` spans, less the `evaluator.call` spans
inside them, as when greedy's restart sampler scores a start point) and
the search loop's cross-round dedup (`round.dedup`), per traced study."""

from bench import spans


def read(ctx):
    if not spans.named(ctx.spans, "round.propose"):
        return None
    engine = sum(spans.self_us(ctx.spans, name, "evaluator.call")
                 for name in ("round.propose", "round.observe"))
    dedup = spans.total_us(spans.named(ctx.spans, "round.dedup"))
    return (engine + dedup) / 1e3 / ctx.studies
