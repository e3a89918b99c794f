"""Scorer kernel: the share of the rows it scores that are real, not
padding up to the pool bucket; counters `scorer.rows` over
`scorer.rows_padded`, summed over the traced studies."""


def read(ctx):
    rows = ctx.counters.get("scorer.rows")
    padded = ctx.counters.get("scorer.rows_padded")
    if rows is None or not padded:
        return None
    return 100.0 * rows / padded
