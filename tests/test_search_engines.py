"""Pluggable search subsystem (repro.core.search)."""

import numpy as np
import pytest

from repro.core import apps
from repro.core.costmodel import AccelConfig, performance_gops
from repro.core.multiapp import AppSpec, run_multiapp_study
from repro.core.search import (AnnealOptimizer, Evaluator, GeneticOptimizer,
                               GreedyOptimizer, RandomSearchOptimizer,
                               make_engine, multi_step_greedy,
                               optimize_for_app, pareto_front_indices,
                               run_search)
from repro.core.space import default_space

from engine_contract import ALL_ENGINES, CONTRACT_CHECKS, run_contract_check


@pytest.fixture(scope="module")
def resnet_spec():
    return AppSpec.from_graph("resnet", apps.build_app("resnet"))


@pytest.fixture(scope="module")
def space():
    return default_space()


def _peaks(spec):
    return dict(peak_weight_bits=spec.peak_weight_bits,
                peak_input_bits=spec.peak_input_bits)


# ------------------------------------------------------------------ evaluator

def test_cached_scores_identical_to_uncached(resnet_spec, space):
    """The LRU cache must be invisible: same scores as direct scoring, in
    any batch composition, on repeat calls."""
    rng = np.random.default_rng(0)
    pool = [space.sample(rng) for _ in range(32)]
    pool = pool + pool[:7]                     # duplicates inside one pool
    ev = Evaluator.for_space(resnet_spec.stream, space, **_peaks(resnet_spec))
    got = ev(pool)

    direct = performance_gops(pool, resnet_spec.stream, space.hw,
                              resnet_spec.peak_weight_bits,
                              resnet_spec.peak_input_bits)
    areas = np.asarray([c.area(space.hw) for c in pool])
    direct = np.where(areas <= space.area_budget, direct, 0.0)
    np.testing.assert_array_equal(got, direct)

    # second call: pure cache hits, identical values
    misses_before = ev.cache_misses
    np.testing.assert_array_equal(ev(pool), direct)
    assert ev.cache_misses == misses_before
    # duplicates + repeats were never re-sent to the model
    assert ev.n_scored == 32


def test_cache_shared_across_restarts(resnet_spec, space):
    res = optimize_for_app(resnet_spec.stream, space, engine="greedy", k=2,
                           restarts=2, seed=0, max_rounds=6,
                           **_peaks(resnet_spec))
    stats = res.evaluator.stats()
    assert stats["cache_hits"] > 0
    assert stats["scored"] < len(res.evaluated)


# ----------------------------------------------------------- greedy bit-exact

GOLD_SINGLE = {"loop_order": 3, "pe_group": 32, "mac_per_group": 32,
               "bank_height": 4096, "bank_width": 128, "weight_banks_pg": 2,
               "act_banks_pg": 2, "tif": 8, "tix": 8, "tiy": 32, "tof": 4,
               "pif": 16, "pof": 4, "pox": 8, "poy": 2, "pkx": 1, "pky": 1,
               "pb": 4}
GOLD_SINGLE_PERF = 369.6940437641056

GOLD_MULTI = {"loop_order": 0, "pe_group": 8, "mac_per_group": 512,
              "bank_height": 8192, "bank_width": 128, "weight_banks_pg": 4,
              "act_banks_pg": 4, "tif": 8, "tix": 64, "tiy": 64, "tof": 16,
              "pif": 2, "pof": 16, "pox": 8, "poy": 2, "pkx": 7, "pky": 1,
              "pb": 4}
GOLD_MULTI_PERF = 835.423693109374


def test_greedy_shim_bit_for_bit(resnet_spec, space):
    """`multi_step_greedy` through the new subsystem reproduces the
    pre-refactor implementation exactly (goldens captured at the seed
    commit: same RNG sequence, same pool construction, same scores)."""
    res = multi_step_greedy(resnet_spec.stream, space, k=2, seed=123,
                            max_rounds=8, **_peaks(resnet_spec))
    assert {k: int(v) for k, v in res.best.asdict().items()} == GOLD_SINGLE
    assert res.best_perf == GOLD_SINGLE_PERF
    assert res.rounds == 2
    assert len(res.evaluated) == 84
    assert len(res.evaluated) == len(res.evaluated_perf)


def test_optimize_for_app_bit_for_bit(resnet_spec, space):
    res = optimize_for_app(resnet_spec.stream, space, engine="greedy", k=2,
                           restarts=2, seed=0, max_rounds=6,
                           **_peaks(resnet_spec))
    assert {k: int(v) for k, v in res.best.asdict().items()} == GOLD_MULTI
    assert res.best_perf == GOLD_MULTI_PERF
    assert len(res.evaluated) == 454


# ----------------------------------------------------------- engine contract

@pytest.mark.parametrize("engine", ALL_ENGINES)
@pytest.mark.parametrize("check", sorted(CONTRACT_CHECKS))
def test_engine_contract(check, engine, resnet_spec, space):
    """Shared harness (tests/engine_contract.py): budget accounting, pool
    validity, NaN/inf tolerance, termination, and seed reproducibility —
    the full (engine x check) matrix over every registered engine."""
    run_contract_check(
        check, engine, space,
        lambda: Evaluator.for_space(resnet_spec.stream, space,
                                    **_peaks(resnet_spec)))


# ------------------------------------------------------------ engine quality

def test_every_engine_beats_random_baseline(resnet_spec, space):
    """Fixed-seed ResNet stream: each real engine must out-search a
    budget-matched-or-smaller pure random baseline."""
    pk = _peaks(resnet_spec)
    baseline = optimize_for_app(resnet_spec.stream, space, engine="random",
                                seed=0, restarts=1, max_rounds=4,
                                engine_kwargs={"batch": 32}, **pk)
    assert baseline.best_perf > 0

    budgets = {
        "greedy": dict(k=3, restarts=2, max_rounds=40),
        "anneal": dict(restarts=2, max_rounds=60,
                       engine_kwargs={"chains": 8}),
        "genetic": dict(restarts=1, max_rounds=12,
                        engine_kwargs={"population": 32}),
    }
    for engine, kw in budgets.items():
        res = optimize_for_app(resnet_spec.stream, space, engine=engine,
                               seed=0, **pk, **kw)
        assert res.best_perf > baseline.best_perf, \
            f"{engine} ({res.best_perf}) <= random ({baseline.best_perf})"


def test_engines_deterministic_given_seed(resnet_spec, space):
    pk = _peaks(resnet_spec)
    for engine in ("anneal", "genetic", "random"):
        a = optimize_for_app(resnet_spec.stream, space, engine=engine,
                             seed=11, restarts=1, max_rounds=6, **pk)
        b = optimize_for_app(resnet_spec.stream, space, engine=engine,
                             seed=11, restarts=1, max_rounds=6, **pk)
        assert a.best_perf == b.best_perf
        assert a.best.asdict() == b.best.asdict()


def test_history_monotone_for_all_engines(resnet_spec, space):
    """Every engine's `history` tracks the incumbent best — nondecreasing."""
    pk = _peaks(resnet_spec)
    for engine in ("greedy", "anneal", "genetic", "random"):
        res = optimize_for_app(resnet_spec.stream, space, engine=engine,
                               seed=3, restarts=1, max_rounds=6, **pk)
        perfs = [p for _, p in res.history]
        assert all(b >= a - 1e-9 for a, b in zip(perfs, perfs[1:])), engine


def test_genetic_offspring_respect_constraints(resnet_spec, space):
    """Constraint-aware crossover/mutation: every offspring generation is
    routed through `repair_for_peaks`, so children satisfy the Eq. 11/13
    buffer floors and the area budget instead of scoring 0 GOPS."""
    ev = Evaluator.for_space(resnet_spec.stream, space,
                             **_peaks(resnet_spec))
    eng = GeneticOptimizer(space, ev, seed=0, population=16, max_rounds=4)
    gen = 0
    while not eng.done:
        pool = eng.propose()
        for cfg in pool:
            assert cfg.weight_buffer_bits() >= resnet_spec.peak_weight_bits
            assert cfg.act_buffer_bits() >= ev.peak_input_bits_scaled, \
                f"gen {gen}: offspring below the Eq. 13 activation floor"
            assert cfg.area(space.hw) <= space.area_budget, \
                f"gen {gen}: offspring violates the area budget"
        eng.observe(pool, ev(pool))
        gen += 1
    assert gen > 1                      # crossover/mutation generations ran
    assert eng.best_perf > 0


def test_genetic_repair_can_be_disabled(resnet_spec, space):
    ev = Evaluator.for_space(resnet_spec.stream, space,
                             **_peaks(resnet_spec))
    eng = GeneticOptimizer(space, ev, seed=0, population=16, max_rounds=3,
                           repair=False)
    saw_invalid = False
    while not eng.done:
        pool = eng.propose()
        saw_invalid = saw_invalid or any(
            c.act_buffer_bits() < ev.peak_input_bits_scaled for c in pool)
        eng.observe(pool, ev(pool))
    # selection-pressure-only mode drifts out of the feasible region
    assert saw_invalid


# ------------------------------------------------------------------- pareto

def test_pareto_front_nondominated(resnet_spec, space):
    pk = _peaks(resnet_spec)
    res = optimize_for_app(resnet_spec.stream, space, engine="genetic",
                           seed=0, restarts=1, max_rounds=8,
                           engine_kwargs={"population": 24}, **pk)
    front = res.pareto_front()
    assert front, "no valid point reached the front"
    # contains the global best-GOPS point
    assert any(pt.perf == res.best_perf for pt in front)
    # pairwise non-domination
    for i, a in enumerate(front):
        for j, b in enumerate(front):
            if i != j:
                assert not (b.perf >= a.perf and b.area <= a.area
                            and (b.perf > a.perf or b.area < a.area)), \
                    "dominated point on the front"
    # every front point beats every evaluated point in perf OR area
    assert all(pt.perf > 0 for pt in front)


def test_pareto_front_indices_simple():
    perf = np.asarray([1.0, 2.0, 3.0, 0.0, 2.5, 1.5])
    area = np.asarray([10., 20., 30., 1.0, 25., 22.])
    # (1,10) (2,20) (2.5,25) (3,30) form the front; (0,1) is excluded as
    # constraint-violating; (1.5,22) is dominated by (2,20)
    assert set(pareto_front_indices(perf, area)) == {0, 1, 4, 2}


# -------------------------------------------------------- multiapp plumbing

def test_multiapp_accepts_engine_name(space):
    specs = [AppSpec.from_graph(n, apps.build_app(n)) for n in ("ptb", "wdl")]
    res = run_multiapp_study(specs, space, k=2, restarts=1, seed=0,
                             max_rounds=4, engine="genetic",
                             engine_kwargs={"population": 16,
                                            "max_rounds": 4})
    assert res.selected is not None
    assert res.perf_matrix.shape == (2, 3)


def test_generic_engines_drive_exec_space():
    """The same engines explore the TPU execution space (DiscreteSpace +
    FunctionEvaluator), with per-point memoization."""
    from repro.core.autotune import autotune_search

    class FakeCell:
        def __init__(self):
            self.n = 0

        def score(self, pt):
            self.n += 1
            return (pt.microbatches * (2 if pt.remat == "dots" else 1)
                    / (1 + abs(pt.attn_kv_block - 2048) / 2048))

    for engine in ("anneal", "genetic", "random"):
        cell = FakeCell()
        best, score = autotune_search(cell, engine=engine, shape_mode="train",
                                      seed=0, max_rounds=5)
        assert score > 0
        assert best.microbatches in (1, 2, 4, 8, 16)
        # memoization: strictly fewer scorer calls than proposals
        assert cell.n <= 5 * 6 + 6


def test_make_engine_factory_and_kwarg_filtering(resnet_spec, space):
    ev = Evaluator.for_space(resnet_spec.stream, space,
                             **_peaks(resnet_spec))
    # unknown kwargs (greedy's k) are dropped for engines that lack them
    eng = make_engine("genetic", space, ev, k=3, population=8, seed=0)
    assert isinstance(eng, GeneticOptimizer)
    eng = make_engine(AnnealOptimizer, space, ev, chains=2, seed=0)
    assert isinstance(eng, AnnealOptimizer)
    res = run_search(make_engine("random", space, ev, batch=8, seed=0,
                                 max_rounds=2), ev)
    assert len(res.evaluated) == 16
