"""Wall seconds per completed study: the first window study's start to
the last one's end, over the studies completed (host clock)."""


def read(ctx):
    return ctx.window.wall_s / ctx.studies
