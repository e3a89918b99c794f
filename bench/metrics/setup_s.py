"""Process start to the first window study: imports, tracing the apps,
and one warm-up study, which compiles on a checkout's first run (host
clock)."""


def read(ctx):
    return ctx.setup_s
