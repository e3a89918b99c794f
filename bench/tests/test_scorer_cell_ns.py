"""Rehearsal of `scorer_cell_ns`, the scorer kernel's device time per
scored cell (padded row x unique op column), on a recorded trace and on
the counter a study on the CPU gives."""

import numpy as np
import pytest

from bench import harness
from bench.trace_reduce import Trace
from repro import obs

# two runs of the scorer program (3 us and 1 us) inside the window
XSPACE = '''
planes {
  id: 1
  name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 3000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 1 offset_ps: 6000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_fused_jax_score(7)" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.1" } }
}
'''


def context(counters, trace=True):
    from jax.profiler import ProfileData
    window = harness.Window(start=0.0, wall_s=1.0, digests=[(1, "d")],
                            configs=0, last={}, study_s=[])
    return harness.Context(
        cell=None, setup_s=0.0, window=window, compile_events=[],
        counters=dict(counters),
        trace=Trace(ProfileData.from_text_proto(XSPACE)) if trace else None,
        trace_window=(0.0, 10000.0))


def test_reader_divides_kernel_time_by_cells():
    read = harness.load_reader("scorer_cell_ns")
    assert read(context({"scorer.cells": 2000})) == \
        pytest.approx(4000 / 2000, rel=1e-12)


@pytest.mark.parametrize("counters, trace", [
    ({"scorer.rows": 96, "scorer.rows_padded": 512}, True),   # the parent
    ({"scorer.cells": 2000}, False),                          # untraced
])
def test_reader_reads_nothing_without_its_sources(counters, trace):
    assert harness.load_reader("scorer_cell_ns")(
        context(counters, trace)) is None


def test_metric_is_listed_for_the_three_screens():
    bench = harness.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    entry = entries["scorer_cell_ns"]
    assert entry["workloads"] == ["deepseek-v2-lite-16b.screen",
                                  "paper-cnn7.screen", "qwen2.5-32b.screen"]
    assert entry["layer"] == entries["scorer_kernel_ms"]["layer"]
    assert entry["moves"] == "study_s" and entry["unit"] == "ns"


def test_a_study_counts_padded_rows_times_op_columns():
    """One app, so each call's cells are its padded rows times that
    app's unique op columns."""
    from repro.core.multiapp import AppSpec
    from repro.dse import SearchBudget, Study
    obs.disable(reset=True)
    try:
        obs.enable(trace=False, metrics=True, journal=False)
        Study(apps=["ptb"], engine="random", backend="jax", seed=0,
              budget=SearchBudget(restarts=1, max_rounds=2,
                                  engine_kwargs={"batch": 12})).run()
        counters = dict(obs.metrics().counters)
    finally:
        obs.disable(reset=True)
    columns = len(AppSpec.from_app("ptb").stream.dedup_columns()[0])
    assert counters["scorer.cells"] == counters["scorer.rows_padded"] \
        * columns
    assert np.isfinite(counters["scorer.cells"])
