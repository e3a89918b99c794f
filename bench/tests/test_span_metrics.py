"""Rehearsals of the readers of the program's own spans and counters
(scorer host path, program builds, engine rounds, evaluator, result
materialisation) on synthetic spans, and of the counters across the
harness's untraced warm-up."""

import pytest

from bench import harness
from repro import obs


def X(name, ts, dur, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "args": args}


# one traced window of two studies (us): every span once, nested as the
# program nests them
SPANS = [
    X("phase.search", 0, 10000),
    X("search_app", 0, 8000, app="a"),
    X("ask_tell_round", 100, 3000),
    X("round.propose", 100, 500),
    X("evaluator.call", 200, 200, n=1),        # a restart sampler's probe
    X("evaluate_batch", 250, 100, n=1),
    X("round.dedup", 600, 100, n=32),
    X("evaluator.call", 700, 2000, n=32),
    X("evaluate_batch", 800, 1800, n=32),
    X("scorer.code", 800, 100, n=32, bucket=256),
    X("scorer.program", 900, 1100, bucket=256, upload=True),
    X("scorer.run", 2000, 500, n=32, bucket=256),
    X("scorer.area", 2500, 50, n=32),
    X("round.observe", 2800, 200, n=32),
    X("search.materialize", 3200, 4000, n=33),
    X("search.export", 8000, 1000, n=33),
    X("study.rebuild", 9000, 800, n=33),
]
COUNTERS = {"scorer.programs": 4, "scorer.rows": 96,
            "scorer.rows_padded": 512}

EXPECTED = {
    "materialize_ms": (4000 + 1000 + 800) / 1e3 / 2,
    # propose less the probe inside it, observe, dedup
    "engine_host_ms": ((500 - 200) + 200 + 100) / 1e3 / 2,
    # both evaluator calls less the device batches inside them
    "evaluator_host_ms": ((200 - 100) + (2000 - 1800)) / 1e3 / 2,
    "scorer_host_ms": (100 + 50) / 1e3 / 2,
    "scorer_wait_ms": 500 / 1e3 / 2,
    "scorer_load_ms": 1100 / 1e3 / 2,
    "programs_per_study": 2.0,
    "scorer_fill_pct": 100.0 * 96 / 512,
}


def context(spans, counters, studies=2):
    window = harness.Window(start=0.0, wall_s=1.0,
                            digests=[(s, "d") for s in range(studies)],
                            configs=0, last={}, study_s=[])
    return harness.Context(cell=None, setup_s=0.0, window=window,
                           compile_events=[], spans=list(spans),
                           counters=dict(counters))


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_reads_its_spans_and_counters(metric):
    got = harness.load_reader(metric)(context(SPANS, COUNTERS))
    assert got == pytest.approx(EXPECTED[metric], rel=1e-12)


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_reads_nothing_from_a_program_without_them(metric):
    """A program older than these spans (the parent of a comparison)
    has only the coarse ones: the reader returns None, not 0."""
    old = [s for s in SPANS if s["name"] in ("phase.search", "search_app",
                                             "ask_tell_round",
                                             "evaluate_batch")]
    counters = {"evaluator.cache_hits": 1, "evaluator.cache_misses": 9}
    assert harness.load_reader(metric)(context(old, counters)) is None


def test_every_new_metric_is_listed_for_every_cell():
    bench = harness.load_benchmark()
    cells = [w["name"] for w in bench["workloads"]]
    entries = {m["name"]: m for m in bench["per_layer"]}
    for metric in EXPECTED:
        assert entries[metric]["workloads"] == cells
        assert entries[metric]["moves"] == "study_s"


def test_warm_up_counts_do_not_reach_the_window():
    """The harness warms up with obs off, then enables it for the traced
    window without a reset: the program counters of the warm-up studies
    must not reach the window's."""
    from repro.dse import SearchBudget, Study
    kw = dict(apps=["ptb", "wdl"], engine="random", backend="jax",
              budget=SearchBudget(restarts=1, max_rounds=2,
                                  engine_kwargs={"batch": 12}), seed=0)
    obs.disable(reset=True)
    try:
        Study(**kw).run()                           # warm-up, obs off
        obs.enable(trace=True, metrics=True, journal=False)
        Study(**kw).run()                           # the window
        spans = [e for e in obs.tracer().export() if e.get("ph") == "X"]
        counters = dict(obs.metrics().counters)
    finally:
        obs.disable(reset=True)
    ctx = context(spans, counters, studies=1)
    assert harness.load_reader("programs_per_study")(ctx) == 2.0
    fill = harness.load_reader("scorer_fill_pct")(ctx)
    assert 0.0 < fill <= 100.0 * 12 / 256
