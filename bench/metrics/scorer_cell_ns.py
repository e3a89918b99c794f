"""Scorer kernel: device nanoseconds per scored cell, the kernel's time in
the profiler trace (as `scorer_kernel_ms` reads it) over the counter
`scorer.cells` (padded rows x unique op columns, summed over the calls of
the traced window).  A cell is one (configuration, op column) entry of the
kernel's gathers, so this tells a wider op table from a slower kernel."""

from bench.harness import SCORER_PROGRAM


def read(ctx):
    cells = ctx.counters.get("scorer.cells")
    if ctx.trace is None or not cells:
        return None
    seconds, runs = ctx.trace.module_time_s(SCORER_PROGRAM,
                                            *ctx.trace_window)
    if runs == 0:
        return None
    return seconds * 1e9 / cells
