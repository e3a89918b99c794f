"""Declarative DSE facade (repro.dse): objective-composition parity with
the legacy pipelines, Pareto front correctness, persistence round-trips,
and the weight-peak-mode plumb."""

import numpy as np
import pytest

from repro.core import apps
from repro.core.multiapp import AppSpec, run_multiapp_study
from repro.core.search import Evaluator
from repro.core.space import default_space
from repro.dse import (AreaBudget, Constraint, GeomeanAcrossApps, MaxPerf,
                       ParetoObjective, PeakBuffers, PerfPerArea,
                       SearchBudget, Study, StudyResult, UserConstraint,
                       make_objective, study_from_cli)


@pytest.fixture(scope="module")
def space():
    return default_space()


@pytest.fixture(scope="module")
def resnet_spec():
    return AppSpec.from_graph("resnet", apps.build_app("resnet"))


@pytest.fixture(scope="module")
def small_specs():
    return [AppSpec.from_graph(n, apps.build_app(n)) for n in ("ptb", "wdl")]


@pytest.fixture(scope="module")
def pareto_result(small_specs, space):
    study = Study(apps=small_specs, space=space,
                  objective=ParetoObjective(["perf", "-area"]),
                  engine="genetic",
                  budget=SearchBudget(restarts=1, max_rounds=6,
                                      engine_kwargs={"population": 20}),
                  area_budgets=(30000.0, 60000.0, 90000.0), seed=0)
    return study.run()


# ------------------------------------------------- parity with the goldens

# Same goldens as tests/test_search_engines.py (captured at the seed
# commit): a MaxPerf Study must reproduce them bit-for-bit.
GOLD_MULTI = {"loop_order": 0, "pe_group": 8, "mac_per_group": 512,
              "bank_height": 8192, "bank_width": 128, "weight_banks_pg": 4,
              "act_banks_pg": 4, "tif": 8, "tix": 64, "tiy": 64, "tof": 16,
              "pif": 2, "pof": 16, "pox": 8, "poy": 2, "pkx": 7, "pky": 1,
              "pb": 4}
GOLD_MULTI_PERF = 835.423693109374

# run_multiapp_study(ptb+wdl, k=2, restarts=2, seed=0, max_rounds=6)
# captured at the PR-4 commit, BEFORE run_multiapp_study became a Study
# composition — pins the Study path to the historical selections.
GOLD_MA_SELECTED = {"loop_order": 2, "pe_group": 64, "mac_per_group": 32,
                    "bank_height": 8192, "bank_width": 16,
                    "weight_banks_pg": 2, "act_banks_pg": 16, "tif": 32,
                    "tix": 32, "tiy": 16, "tof": 16, "pif": 8, "pof": 16,
                    "pox": 16, "poy": 2, "pkx": 7, "pky": 1, "pb": 4}
GOLD_MA_GEOMEANS = [1.0000000000000004e-06, 0.967758135970744,
                    0.9954428121972676]
GOLD_MA_NCAND = {"ptb": 23, "wdl": 54}


def test_maxperf_study_reproduces_greedy_goldens(resnet_spec, space):
    study = Study(apps=[resnet_spec], space=space, objective=MaxPerf(),
                  engine="greedy",
                  budget=SearchBudget(k=2, restarts=2, max_rounds=6),
                  seed=0)
    res = study.run()
    assert {k: int(v) for k, v in res.best.asdict().items()} == GOLD_MULTI
    assert res.best_score == GOLD_MULTI_PERF
    assert res.per_app["resnet"]["n_evaluated"] == 454


def test_geomean_study_reproduces_multiapp_golden(small_specs, space):
    """Both front doors — the legacy `run_multiapp_study` signature and a
    hand-built `GeomeanAcrossApps` Study — reproduce the pre-refactor
    Table-4 selections byte-for-byte."""
    ma = run_multiapp_study(small_specs, space, k=2, restarts=2, seed=0,
                            max_rounds=6)
    assert {k: int(v)
            for k, v in ma.selected.asdict().items()} == GOLD_MA_SELECTED
    assert ma.geomeans.tolist() == GOLD_MA_GEOMEANS
    assert {a: len(ma.candidates_per_app[a])
            for a in ma.apps} == GOLD_MA_NCAND

    res = Study(apps=small_specs, space=space,
                objective=GeomeanAcrossApps(), engine="greedy",
                budget=SearchBudget(k=2, restarts=2, max_rounds=6),
                seed=0).run()
    assert {k: int(v)
            for k, v in res.best.asdict().items()} == GOLD_MA_SELECTED
    assert res.multiapp_summary["geomeans"] == GOLD_MA_GEOMEANS


# ------------------------------------------------------- objectives (unit)

def test_objective_registry_and_scores():
    metrics = {"perf": np.asarray([100.0, 0.0, 50.0]),
               "area": np.asarray([10.0, 5.0, 100.0])}
    assert np.array_equal(make_objective("maxperf").score(metrics),
                          metrics["perf"])
    ppa = make_objective("perf-per-area").score(metrics)
    np.testing.assert_allclose(ppa, [10.0, 0.0, 0.5])
    cross = np.asarray([[4.0, 1.0, 0.0], [9.0, 1.0, 5.0]])
    geo = make_objective("geomean").score({"perf_matrix": cross})
    np.testing.assert_allclose(geo, [6.0, 1.0, 0.0])  # col 3 invalid on app0
    with pytest.raises(ValueError):
        make_objective("nope")


@pytest.mark.parametrize("method", ["chebyshev", "hypervolume"])
def test_pareto_scalarization_orders_sensibly(method):
    obj = ParetoObjective(["perf", "-area"], method=method)
    metrics = {"perf": np.asarray([100.0, 100.0, 0.0, 60.0]),
               "area": np.asarray([50.0, 80.0, 1.0, 50.0])}
    values = obj.values(metrics)
    assert values.shape == (4, 2)
    s = obj.scalarize(values)
    # infeasible (perf=0) rows scalarize to exactly 0, feasible to > 0
    assert s[2] == 0.0
    assert (s[[0, 1, 3]] > 0).all()
    # row 0 dominates rows 1 (same perf, more area) and 3 (less perf,
    # same area): any sane scalarization ranks it strictly first
    assert s[0] > s[1]
    assert s[0] > s[3]


def test_pareto_objective_validation():
    with pytest.raises(ValueError):
        ParetoObjective(["perf"])                      # < 2 terms
    with pytest.raises(ValueError):
        ParetoObjective(["perf", "-area"], method="magic")
    with pytest.raises(ValueError):
        ParetoObjective(["-perf", "-area"])            # no maximize term


def test_pareto_study_rejects_terms_outside_perf_area(small_specs, space):
    """App-mode synthesis only knows perf/area; custom terms must error at
    construction, not silently vanish from the persisted front."""
    with pytest.raises(ValueError, match="perf"):
        Study(apps=small_specs, space=space,
              objective=ParetoObjective(["perf", "-area", "-energy"]))


def test_evaluator_mode_rejects_unapplied_objective_and_constraints():
    """Evaluator-mode scoring is owned by the supplied evaluator: passing
    objective/constraints there would be recorded but never applied, so
    the Study refuses them up front."""
    from repro.core.search import DiscreteSpace, FunctionEvaluator
    space = DiscreteSpace(domains={"x": (1, 2, 4)},
                          make_config=lambda **kw: kw["x"])
    fev = FunctionEvaluator(lambda cfg: float(cfg))
    with pytest.raises(ValueError, match="evaluator"):
        Study(space=space, evaluator=fev,
              objective=ParetoObjective(["perf", "-area"]))
    with pytest.raises(ValueError, match="evaluator"):
        Study(space=space, evaluator=fev,
              constraints=[AreaBudget(1.0)])


# -------------------------------------------------- pareto study + sweep

def test_pareto_study_front_nondominated(pareto_result):
    front = pareto_result.front
    assert front, "no point reached the joint front"
    for i, a in enumerate(front):
        for j, b in enumerate(front):
            if i != j:
                assert not (b.score >= a.score and b.area <= a.area
                            and (b.score > a.score or b.area < a.area)), \
                    "dominated point on the front"
    assert all(p.score > 0 for p in front)
    # per-app GOPS columns ride along for Table-3-style reporting
    assert all(set(p.per_app) == {"ptb", "wdl"} for p in front)


def test_pareto_per_app_best_perf_is_gops(pareto_result):
    """per_app['best_perf'] stays in GOPS for vector objectives (the
    scalarized search signal lands in 'best_scalarized'), so the field is
    comparable across objectives."""
    for rec in pareto_result.per_app.values():
        assert rec["best_perf"] > 10.0          # GOPS scale, not ~[0, 1.1]
        assert 0.0 < rec["best_scalarized"] <= 1.2


def test_pareto_study_budget_selections(pareto_result):
    sels = pareto_result.budget_selections
    assert len(sels) == 3                      # >= 3 area budgets swept
    front = pareto_result.front
    for b, sel in sels.items():
        if sel is None:
            continue
        assert sel["area"] <= float(b)
        # the selection is the best front point inside the budget
        best = max((p.score for p in front if p.area <= float(b)),
                   default=0.0)
        assert sel["score"] == best
    assert any(sel is not None for sel in sels.values())


def test_pareto_study_rerun_is_reproducible(small_specs, space):
    """The scalarizer's running normalization bounds are per-run state:
    calling .run() twice on one Study (or sharing one objective across
    apps) must not change the outcome."""
    study = Study(apps=small_specs, space=space,
                  objective=ParetoObjective(["perf", "-area"]),
                  engine="genetic",
                  budget=SearchBudget(restarts=1, max_rounds=4,
                                      engine_kwargs={"population": 12}),
                  seed=3)
    a, b = study.run(), study.run()
    assert a.to_json() == b.to_json()


def test_study_result_save_load_roundtrip(pareto_result, tmp_path):
    p = pareto_result.save(tmp_path / "study.json")
    loaded = StudyResult.load(p)
    assert loaded.to_json() == pareto_result.to_json()
    assert loaded.best.asdict() == pareto_result.best.asdict()
    assert loaded.meta["objective"]["name"] == "pareto"
    assert [pt.config.asdict() for pt in loaded.front] == \
        [pt.config.asdict() for pt in pareto_result.front]


# -------------------------------------------- constraints + injection

def test_evaluator_objective_and_constraint_injection(resnet_spec, space):
    rng = np.random.default_rng(0)
    pool = [space.sample(rng) for _ in range(24)]
    base = Evaluator.for_space(resnet_spec.stream, space,
                               peak_input_bits=resnet_spec.peak_input_bits)
    gops, area = base.score_with_area(pool)

    ppa = Evaluator.for_space(resnet_spec.stream, space,
                              peak_input_bits=resnet_spec.peak_input_bits,
                              objective=PerfPerArea())
    np.testing.assert_allclose(ppa(pool), gops / np.maximum(area, 1e-12))

    half = UserConstraint(
        lambda batch, metrics: metrics["area"] <= space.area_budget / 2,
        name="half-area")
    tight = Evaluator.for_space(resnet_spec.stream, space,
                                peak_input_bits=resnet_spec.peak_input_bits,
                                constraints=[half])
    got = tight(pool)
    np.testing.assert_array_equal(
        got, np.where(area <= space.area_budget / 2, gops, 0.0))


def test_peak_buffers_constraint_unifies_mask_and_repair(resnet_spec, space):
    from repro.core.costmodel import ConfigBatch
    rng = np.random.default_rng(1)
    pool = [space.sample(rng) for _ in range(32)]
    batch = ConfigBatch.from_configs(pool)
    ev = Evaluator.for_space(resnet_spec.stream, space,
                             peak_input_bits=resnet_spec.peak_input_bits)
    pb = PeakBuffers(weight_bits=0, input_bits=ev.peak_input_bits_scaled)
    mask = pb.feasible_mask(batch, {})
    expect = np.asarray([c.act_buffer_bits() >= ev.peak_input_bits_scaled
                         for c in pool])
    np.testing.assert_array_equal(mask, expect)
    repaired = pb.repair(batch, space)
    assert pb.feasible_mask(repaired, {}).all()
    # repair routed through the space also re-enters the area budget
    from repro.core.costmodel import area_many
    assert (area_many(repaired, space.hw) <= space.area_budget).all()


def test_selection_stage_honors_injected_constraints(small_specs, space):
    """The geomean winner must satisfy the Study's declared constraints:
    the cross-evaluation matrix zeroes columns the extra constraints
    reject, so an infeasible candidate can never be 'valid on every
    app'."""
    cap = UserConstraint(
        lambda batch, metrics: batch.col("pe_group") <= 16,
        name="pe-cap")
    res = Study(apps=small_specs, space=space,
                objective=GeomeanAcrossApps(), engine="greedy",
                constraints=[cap],
                budget=SearchBudget(k=2, restarts=1, max_rounds=4),
                seed=0).run()
    assert res.best.pe_group <= 16
    for pt_cfg in [res.multiapp.selected] + \
            [res.multiapp.best_per_app[a] for a in res.multiapp.apps
             if res.multiapp.best_perf_per_app[a] > 0]:
        assert pt_cfg.pe_group <= 16


def test_repair_plumbing_chains_constraint_repairs(resnet_spec, space):
    """Engine repair (`repair_with`/`repair_many_with`) runs the injected
    constraints' repair hooks after the space's peak repair."""
    import dataclasses as dc

    from repro.core.costmodel import ConfigBatch
    from repro.core.search import repair_many_with, repair_with
    from repro.dse import Constraint

    class PinLoopOrder(Constraint):
        name = "pin-loop-order"

        def feasible_mask(self, batch, metrics):
            return batch.col("loop_order") == 0

        def repair(self, batch, space):
            m = batch.matrix.copy()
            m[:, ConfigBatch._INDEX["loop_order"]] = 0
            return ConfigBatch(m)

    ev = Evaluator.for_space(resnet_spec.stream, space,
                             peak_input_bits=resnet_spec.peak_input_bits,
                             constraints=[PinLoopOrder()])
    rng = np.random.default_rng(0)
    cfg = dc.replace(space.sample(rng), loop_order=3)
    assert repair_with(space, ev, cfg).loop_order == 0
    batch = ConfigBatch.from_configs([cfg] * 5)
    repaired = repair_many_with(space, ev, batch)
    assert (repaired.col("loop_order") == 0).all()


def test_area_budget_constraint_overrides_space(resnet_spec, space):
    tight = Study(apps=[resnet_spec], space=space, objective=MaxPerf(),
                  constraints=[AreaBudget(30000.0)], engine="random",
                  budget=SearchBudget(restarts=1, max_rounds=3,
                                      engine_kwargs={"batch": 16}),
                  seed=0).run()
    assert tight.meta["area_budget"] == 30000.0
    if tight.best is not None and tight.best_score > 0:
        assert tight.best.area(space.hw) <= 30000.0


# ------------------------------------------------- weight-peak-mode plumb

def test_weight_peak_mode_hand_built():
    strict = AppSpec.from_app("wdl", weight_peak_mode="strict")
    streaming = AppSpec.from_app("wdl", weight_peak_mode="streaming")
    assert strict.peak_weight_bits > 0
    assert streaming.peak_weight_bits == 0
    assert strict.peak_input_bits == streaming.peak_input_bits > 0
    with pytest.raises(ValueError):
        AppSpec.from_app("wdl", weight_peak_mode="sideways")


def test_weight_peak_mode_traced_zoo():
    """Traced `<arch>:decode` apps cost under both Eq. 10/11 readings."""
    pytest.importorskip("jax")
    strict = AppSpec.from_app("qwen2-0.5b:decode", weight_peak_mode="strict")
    streaming = AppSpec.from_app("qwen2-0.5b:decode",
                                 weight_peak_mode="streaming")
    assert strict.peak_weight_bits > 0
    assert streaming.peak_weight_bits == 0
    assert strict.peak_input_bits == streaming.peak_input_bits > 0
    # the strict floor changes feasibility: strict-mode evaluation zeroes
    # configs whose weight buffer cannot hold the largest layer
    space = default_space()
    rng = np.random.default_rng(0)
    pool = [space.sample(rng) for _ in range(16)]
    ev_strict = Evaluator.for_space(strict.stream, space,
                                    peak_weight_bits=strict.peak_weight_bits,
                                    peak_input_bits=strict.peak_input_bits)
    ev_stream = Evaluator.for_space(
        streaming.stream, space,
        peak_input_bits=streaming.peak_input_bits)
    s_strict, s_stream = ev_strict(pool), ev_stream(pool)
    assert (s_strict <= s_stream + 1e-9).all()


# --------------------------------------------------------------- CLI

def test_study_from_cli_builds_study():
    study, args = study_from_cli(["--apps", "ptb", "--apps", "wdl",
                                  "--engine", "genetic", "--smoke",
                                  "--engine-kwarg", "population=20"])
    assert [s.name for s in study.specs] == ["ptb", "wdl"]
    assert study.objective.name == "geomean"       # default for >1 app
    assert study.engine == "genetic"
    assert study.budget.restarts == 1              # smoke budget
    assert study.budget.engine_kwargs["population"] == 20

    study, _ = study_from_cli(["--apps", "resnet", "--objective", "pareto",
                               "--budgets", "30000", "--budgets", "60000",
                               "--budgets", "90000", "--area-budget",
                               "90000"])
    assert study.objective.name == "pareto"
    assert study.area_budgets == (30000.0, 60000.0, 90000.0)
    with pytest.raises(SystemExit):
        study_from_cli(["--engine-kwarg", "nonsense"])


def test_study_from_cli_explicit_flags_beat_smoke():
    study, _ = study_from_cli(["--apps", "resnet", "--smoke",
                               "--restarts", "8", "--max-rounds", "9"])
    assert study.budget.restarts == 8              # explicit wins
    assert study.budget.max_rounds == 9
    assert study.budget.k == 2                     # smoke fills the rest
    # --budgets without a pareto objective is an error, not a silent drop
    with pytest.raises(ValueError, match="area_budgets"):
        study_from_cli(["--apps", "resnet", "--budgets", "30000"])


def test_compile_cache_dir_follows_env(monkeypatch):
    """Entry points keep JAX's compile cache where JAX_COMPILATION_CACHE_DIR
    says (configuring nothing else), or else at a fixed, git-ignored
    `<checkout>/.jax_cache`."""
    from pathlib import Path

    from repro.dse.cli import compile_cache_dir
    root = Path(__file__).resolve().parents[1]
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(root / "elsewhere"))
    assert compile_cache_dir() is None
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache_dir() == root / ".jax_cache"
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
