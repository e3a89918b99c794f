"""Scorer kernel's share of its HBM roofline: the bytes every scorer call
of the traced studies has to move (`bench/roofline.py`, from the rows
of each `evaluate_batch` span and the op count of the app whose
`search_app` span holds it) at the chip's peak bandwidth, over the
kernel's device time."""

from bench import roofline, spans
from bench.harness import SCORER_PROGRAM


def read(ctx):
    if ctx.trace is None:
        return None
    seconds, runs = ctx.trace.module_time_s(SCORER_PROGRAM,
                                            *ctx.trace_window)
    if runs == 0 or seconds <= 0:
        return None
    n_ops = ctx.app_ops()
    n_vars = len(ctx.cell.config["domains"])
    searches = spans.named(ctx.spans, "search_app")
    total = 0
    for call in spans.named(ctx.spans, "evaluate_batch"):
        app = next((s["args"]["app"] for s in searches
                    if spans.inside(s, call)), None)
        if app is None:
            continue
        total += roofline.call_bytes(call["args"]["n"], n_vars, n_ops[app])
    if total == 0:
        return None
    return roofline.hbm_roofline_pct(total, seconds, ctx.device_kind)
