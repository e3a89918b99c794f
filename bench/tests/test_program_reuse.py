"""Rehearsal of `program_reuse_pct`, the share of the scorer's program
lookups that found a compiled program of the same shapes, on synthetic
counters and across the harness's untraced warm-up."""

import pytest

from bench import harness
from repro import obs


def context(counters, studies=1):
    window = harness.Window(start=0.0, wall_s=1.0,
                            digests=[(s, "d") for s in range(studies)],
                            configs=0, last={}, study_s=[])
    return harness.Context(cell=None, setup_s=0.0, window=window,
                           compile_events=[], counters=dict(counters))


@pytest.mark.parametrize("counters, expected", [
    ({"scorer.programs": 0, "scorer.program_reuses": 11}, 100.0),
    ({"scorer.programs": 3, "scorer.program_reuses": 1}, 25.0),
    ({"scorer.programs": 2, "scorer.program_reuses": 0}, 0.0),
])
def test_reader_reads_its_counters(counters, expected):
    got = harness.load_reader("program_reuse_pct")(context(counters))
    assert got == pytest.approx(expected, rel=1e-12)


def test_reader_reads_nothing_from_a_program_without_the_counter():
    """A program that compiles a program per scorer (the parent of a
    comparison) has no `scorer.program_reuses`: None, not 0."""
    read = harness.load_reader("program_reuse_pct")
    assert read(context({"scorer.programs": 2})) is None
    assert read(context({})) is None


def test_metric_is_listed_for_every_cell():
    bench = harness.load_benchmark()
    entry = {m["name"]: m for m in bench["per_layer"]}["program_reuse_pct"]
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]]
    assert entry["moves"] == "study_s"


def test_window_studies_reuse_the_warm_up_programs():
    """After an untraced warm-up study, a window study builds no program
    and finds every one it needs."""
    from repro.dse import SearchBudget, Study
    kw = dict(apps=["ptb", "wdl"], engine="random", backend="jax",
              budget=SearchBudget(restarts=1, max_rounds=2,
                                  engine_kwargs={"batch": 12}), seed=0)
    obs.disable(reset=True)
    try:
        Study(**kw).run()                           # warm-up, obs off
        obs.enable(trace=True, metrics=True, journal=False)
        Study(**kw).run()                           # the window
        counters = dict(obs.metrics().counters)
    finally:
        obs.disable(reset=True)
    ctx = context(counters)
    assert harness.load_reader("programs_per_study")(ctx) == 0.0
    assert harness.load_reader("program_reuse_pct")(ctx) == 100.0
