"""Scorer kernel (the jitted `fused_jax_score` program): its device time
in the profiler trace, per traced study."""

from bench.harness import SCORER_PROGRAM


def read(ctx):
    if ctx.trace is None:
        return None
    seconds, runs = ctx.trace.module_time_s(SCORER_PROGRAM,
                                            *ctx.trace_window)
    if runs == 0:
        return None
    return seconds * 1e3 / ctx.studies
