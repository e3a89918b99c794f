"""Study search phase outside the engine rounds (`dse/study.py`,
`dse/parallel.py`, `core/search/base.py` after its loop): `phase.search`
spans minus the `ask_tell_round` spans inside them, per traced study."""

from bench import spans


def read(ctx):
    if not spans.named(ctx.spans, "phase.search"):
        return None
    return (spans.self_us(ctx.spans, "phase.search", "ask_tell_round")
            / 1e3 / ctx.studies)
