"""A run with the timed path broken underneath must come out incorrect.

The harness's look for a chip is replaced (JAX runs on the CPU here) and
the rest of a run is driven at a size a test can hold: two CNN apps, a
random screen of two 256-config rounds.  Each fault this kind of cell can
have (`bench/faults.py`) is planted in the device scorer; the
lower-precision control (the float32 reference in the scorer's place)
must fail too.  One chip has no exchange between chips to leave out.
"""

import time

import pytest

from bench import control, faults, harness

LIMITS = {"gops_gap": 1e-9, "area_gap": 1e-9, "best_gap": 1e-9,
          "select_gap": 1e-9, "studies_differ": 0, "stream_drift": 0}


@pytest.fixture(autouse=True)
def cpu_chip(monkeypatch):
    import jax
    monkeypatch.setattr(harness, "find_chips",
                        lambda chips: jax.devices()[:chips])


def _cell(objective="geomean", **extra):
    conf = harness.load_cell("paper-cnn7.greedy").config
    conf = dict(conf, apps=["resnet", "inception"])
    traffic = {"name": "tiny.screen", "engine": "random",
               "objective": objective, "k": 3, "restarts": 1,
               "max_rounds": 2, "top_frac": 0.1,
               "max_candidates_per_app": 200,
               "engine_kwargs": {"batch": 256},
               "limits": dict(LIMITS), **extra}
    if objective == "pareto":
        traffic["budgets"] = [60000.0, 90000.0, 120000.0]
        del traffic["limits"]["best_gap"]
    return harness.Cell("tiny.screen", 1, conf, traffic, [], [])


def _run(cell):
    line, _ = harness.run(cell, seed=2147483713, seconds=0, trace=False,
                          process_start=time.perf_counter())
    return line


@pytest.mark.parametrize("objective", ["geomean", "pareto"])
def test_sound_run_is_correct(objective):
    line = _run(_cell(objective))
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    assert line["checks"]["gops_gap"]["value"] < 1e-12
    assert line["checks"]["stream_drift"]["value"] == 0


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_planted_fault_is_not_correct(monkeypatch, fault):
    faults.install(fault, monkeypatch.setattr)
    line = _run(_cell())
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("objective", ["geomean", "pareto"])
def test_float32_control_is_not_correct(monkeypatch, objective):
    control.install_control(monkeypatch.setattr)
    line = _run(_cell(objective))
    assert not line["correct"]
    for name in ("gops_gap", "area_gap", "select_gap"):
        assert line["checks"][name]["value"] > 1e-9, name


def test_the_window_holds_whole_passes_over_the_study_seeds():
    line = _run(_cell(study_seeds=[2147483901, 2147483902, 2147483903]))
    assert line["correct"], line["checks"]
    assert line["attempted"] == 3


def test_a_fault_in_an_earlier_study_of_the_pass_is_caught(monkeypatch):
    """Every seed's study is checked, not only the window's last."""
    from repro.kernels.costmodel import FusedJaxScorer
    seeds = [2147483901, 2147483902]
    built, sound = [], FusedJaxScorer.metrics
    altered = faults.answer_altered(sound)
    build = harness.build_study

    def counting_build(cell, seed):
        built.append(seed)
        return build(cell, seed)

    def metrics(self, matrix):
        # the window's first study is the build after the warm-up pass
        fn = altered if len(built) == len(seeds) + 1 else sound
        return fn(self, matrix)

    monkeypatch.setattr(harness, "build_study", counting_build)
    monkeypatch.setattr(FusedJaxScorer, "metrics", metrics)
    line = _run(_cell(study_seeds=seeds))
    assert line["attempted"] == 2
    assert not line["correct"], line["checks"]
    assert line["checks"]["gops_gap"]["value"] > 1e-9


def test_a_drifted_op_stream_is_not_correct():
    cell = _cell()
    streams = {k: dict(v) for k, v in cell.config["streams"].items()}
    streams["resnet"]["peak_input_bits"] += 8
    cell.config = dict(cell.config, streams=streams)
    line = _run(cell)
    assert not line["correct"]
    assert line["checks"]["stream_drift"]["value"] == 1
