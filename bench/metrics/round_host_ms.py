"""Engine rounds on the host (`core/search/base.py`, the engines,
`evaluator.py`): `ask_tell_round` spans minus the `evaluate_batch` spans
inside them, per traced study."""

from bench import spans


def read(ctx):
    if not spans.named(ctx.spans, "ask_tell_round"):
        return None
    return (spans.self_us(ctx.spans, "ask_tell_round", "evaluate_batch")
            / 1e3 / ctx.studies)
