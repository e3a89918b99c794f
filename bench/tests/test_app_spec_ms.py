"""Rehearsal of the `app_spec_ms` reader on synthetic spans: the app specs
each `Study` builds when it is constructed (`study.app_specs`), per
traced study."""

import pytest

from bench import harness


def X(name, ts, dur, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "args": args}


# one traced window of two studies (us): each builds its specs, then
# searches and synthesises
SPANS = [
    X("phase.search", 500, 9000, apps=2),
    X("phase.synthesize", 9600, 300),
    X("phase.search", 10500, 9000, apps=2),
    X("phase.synthesize", 19600, 300),
]
SPECS = [X("study.app_specs", 100, 350, apps=2, nodes=7000),
         X("study.app_specs", 10000, 450, apps=2, nodes=7000)]


def context(spans, studies=2):
    window = harness.Window(start=0.0, wall_s=1.0,
                            digests=[(s, "d") for s in range(studies)],
                            configs=0, last={}, study_s=[])
    return harness.Context(cell=None, setup_s=0.0, window=window,
                           compile_events=[], spans=list(spans))


def test_app_spec_ms_sums_the_spans_per_study():
    read = harness.load_reader("app_spec_ms")
    assert read(context(SPANS + SPECS)) == pytest.approx(
        (350 + 450) / 1e3 / 2, rel=1e-12)


def test_app_spec_ms_reads_nothing_from_a_program_without_them():
    """A program older than the span (the parent of a comparison): None,
    not 0, so the line leaves the metric out."""
    assert harness.load_reader("app_spec_ms")(context(SPANS)) is None


def test_app_spec_ms_is_listed_for_every_cell():
    """Every study builds its app specs, whatever its engine."""
    bench = harness.load_benchmark()
    entry = {m["name"]: m for m in bench["per_layer"]}["app_spec_ms"]
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]]
    assert entry["moves"] == "study_s"
    assert entry["source"] == "program_span"
    assert entry["layer"] == "Study construction (dse/study.py)"


def test_a_study_records_its_app_spec_span():
    """The program records the span the reader reads, once per study."""
    from repro import obs
    from repro.dse import SearchBudget, Study
    obs.disable(reset=True)
    try:
        obs.enable(trace=True, metrics=False, journal=False)
        Study(apps=["ptb"], engine="random", backend="numpy", seed=0,
              budget=SearchBudget(restarts=1, max_rounds=1,
                                  engine_kwargs={"batch": 8})).run()
        spans = [e for e in obs.tracer().export() if e.get("ph") == "X"]
    finally:
        obs.disable(reset=True)
    window = harness.Window(start=0.0, wall_s=1.0, digests=[(0, "d")],
                            configs=0, last={}, study_s=[])
    ctx = harness.Context(cell=None, setup_s=0.0, window=window,
                          compile_events=[], spans=spans)
    assert harness.load_reader("app_spec_ms")(ctx) > 0
