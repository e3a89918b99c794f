"""Rehearsal of the `repair_ms` reader on synthetic spans: the batched peak
repair inside the engines' proposals (`space.repair`), per traced study."""

import pytest

from bench import harness


def X(name, ts, dur, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "args": args}


# one traced window of two studies (us): a proposal round with two
# batched repairs inside it
SPANS = [
    X("phase.search", 0, 10000),
    X("search_app", 0, 8000, app="a"),
    X("ask_tell_round", 100, 6000),
    X("round.propose", 100, 5000),
    X("evaluator.call", 5200, 500, n=32),
    X("evaluate_batch", 5250, 400, n=32),
]
REPAIRS = [X("space.repair", 150, 40, n=32),
           X("space.repair", 5000, 60, n=32)]


def context(spans, studies=2):
    window = harness.Window(start=0.0, wall_s=1.0,
                            digests=[(s, "d") for s in range(studies)],
                            configs=0, last={}, study_s=[])
    return harness.Context(cell=None, setup_s=0.0, window=window,
                           compile_events=[], spans=list(spans),
                           counters={"repair.rows": 64})


def test_repair_ms_reads_the_repair_spans():
    read = harness.load_reader("repair_ms")
    assert read(context(SPANS + REPAIRS)) == pytest.approx(
        (40 + 60) / 1e3 / 2, rel=1e-12)


def test_repair_ms_reads_nothing_from_a_program_without_them():
    """A program older than the span (the parent of a comparison): None,
    not 0, so the line leaves the metric out."""
    assert harness.load_reader("repair_ms")(context(SPANS)) is None


def test_repair_ms_is_listed_for_the_cells_that_repair_in_batches():
    """Greedy repairs one config at a time and never calls the batched
    repair, so it is not listed."""
    entry = {m["name"]: m for m in harness.load_benchmark()["per_layer"]
             }["repair_ms"]
    assert entry["moves"] == "study_s"
    assert entry["source"] == "program_span"
    assert entry["workloads"] == ["qwen2.5-32b.screen", "qwen2.5-32b.pareto",
                                  "deepseek-v2-lite-16b.screen",
                                  "paper-cnn7.screen"]
