"""Evaluator row cache (`Evaluator.__call__`): row hashing, in-pool
dedup, the `RowHashCache` probe and insert, the feasibility mask and
the objective; the `evaluator.call` spans less the `evaluate_batch`
spans inside them, per traced study."""

from bench import spans


def read(ctx):
    if not spans.named(ctx.spans, "evaluator.call"):
        return None
    return (spans.self_us(ctx.spans, "evaluator.call", "evaluate_batch")
            / 1e3 / ctx.studies)
