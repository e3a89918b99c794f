"""Parallel, resumable Study execution (repro.dse.parallel): checkpoint /
resume bit-equivalence across engines and crash points, worker fault
tolerance (retry + serial degradation), and determinism of every parallel
reduce (worker count, shard order, sharded cross-eval)."""

import json
import random

import numpy as np
import pytest

from repro.core.multiapp import AppSpec
from repro.core.space import default_space
from repro.dse import (FaultPlan, GeomeanAcrossApps, MaxPerf,
                       ParallelExecutionWarning, ParallelExecutor,
                       ParetoObjective, SearchBudget, Study,
                       canonical_front_indices, merge_pareto_fronts)
from test_dse_study import GOLD_MA_GEOMEANS, GOLD_MA_SELECTED, GOLD_MULTI, \
    GOLD_MULTI_PERF

SMALL = dict(apps=["ptb", "wdl"], engine="greedy",
             budget=SearchBudget(k=2, restarts=1, max_rounds=3), seed=0)


def result_bytes(result) -> str:
    return json.dumps(result.to_json(), sort_keys=True)


def run_study(**overrides) -> str:
    kw = dict(SMALL)
    kw.update(overrides)
    return result_bytes(Study(**kw).run())


class Crash(Exception):
    pass


# ------------------------------------------------ resume bit-equivalence

ENGINE_BUDGETS = {
    "greedy": SearchBudget(k=2, restarts=1, max_rounds=3),
    "anneal": SearchBudget(restarts=1, max_rounds=4,
                           engine_kwargs={"chains": 3}),
    "genetic": SearchBudget(restarts=1, max_rounds=4,
                            engine_kwargs={"population": 12}),
    "random": SearchBudget(restarts=1, max_rounds=3,
                           engine_kwargs={"batch": 12}),
    "tpe": SearchBudget(restarts=1, max_rounds=4,
                        engine_kwargs={"batch": 12, "startup_rounds": 1}),
    "nsga2": SearchBudget(restarts=1, max_rounds=4,
                          engine_kwargs={"population": 12}),
}


@pytest.mark.parametrize("engine", sorted(ENGINE_BUDGETS))
def test_resume_is_bit_identical_at_every_boundary(engine, tmp_path):
    """Kill the study right after each checkpoint write; `Study.resume`
    must produce JSON byte-identical to the uninterrupted run — for every
    engine and every crash point (including after the final per-app
    search, i.e. before synthesis)."""
    kw = dict(apps=["ptb", "wdl"], engine=engine,
              budget=ENGINE_BUDGETS[engine], seed=0)
    baseline = result_bytes(Study(**kw).run())

    for boundary in (1, 2):
        ckpt = tmp_path / f"{engine}.{boundary}.ckpt"

        def boom(n, stop=boundary):
            if n == stop:
                raise Crash

        with pytest.raises(Crash):
            Study(**kw).run(checkpoint_path=ckpt, checkpoint_every=1,
                            on_checkpoint=boom)
        assert ckpt.exists(), "crash must leave the checkpoint behind"
        frag = json.loads(ckpt.read_text())
        assert frag["kind"] == "study-checkpoint"
        assert len(frag["completed"]) == boundary

        resumed = Study.resume(ckpt)
        assert result_bytes(resumed) == baseline
        assert not ckpt.exists(), "checkpoint must be removed on success"


def test_resume_under_parallel_workers(tmp_path):
    """Crash a parallel run, resume with a different worker count: still
    byte-identical (execution knobs are not part of the problem)."""
    baseline = run_study()
    ckpt = tmp_path / "par.ckpt"

    def boom(n):
        if n == 1:
            raise Crash

    with pytest.raises(Crash):
        Study(workers=2, **SMALL).run(checkpoint_path=ckpt,
                                      checkpoint_every=1, on_checkpoint=boom)
    assert result_bytes(Study.resume(ckpt, workers=1)) == baseline


def test_resume_roundtrips_customized_vector_objective(tmp_path):
    """A checkpoint holding a `ParetoObjective` with non-default
    scalarizer kwargs (method, weights, rho) must rebuild the *same*
    objective — the full `describe()` spec round-trips, not just the
    defaults — and resume to a byte-identical result."""
    obj = ParetoObjective(method="hypervolume", weights=[2.0, 1.0],
                          rho=0.2)
    kw = dict(apps=["ptb", "wdl"], engine="genetic", objective=obj,
              budget=SearchBudget(restarts=1, max_rounds=4,
                                  engine_kwargs={"population": 12}),
              seed=0)
    baseline = result_bytes(Study(**kw).run())
    spec = obj.describe()
    assert spec == {"name": "pareto", "terms": ["perf", "-area"],
                    "method": "hypervolume", "weights": [2.0, 1.0],
                    "rho": 0.2}
    ckpt = tmp_path / "vec.ckpt"

    def boom(n):
        if n == 1:
            raise Crash

    with pytest.raises(Crash):
        Study(**kw).run(checkpoint_path=ckpt, checkpoint_every=1,
                        on_checkpoint=boom)
    assert json.loads(ckpt.read_text())["study"]["objective"] == spec
    resumed = Study.resume(ckpt)
    assert resumed.meta["objective"] == spec
    assert result_bytes(resumed) == baseline


def test_checkpoint_requires_rebuildable_spec(tmp_path):
    """AppSpec objects / engine factories cannot round-trip through JSON:
    checkpointing fails fast, before any search runs."""
    spec = AppSpec.from_app("ptb")
    study = Study(apps=[spec], objective=MaxPerf(),
                  budget=SearchBudget(restarts=1, max_rounds=2))
    with pytest.raises(ValueError, match="AppSpec"):
        study.run(checkpoint_path=tmp_path / "x.ckpt")
    assert not (tmp_path / "x.ckpt").exists()

    with pytest.raises(ValueError, match="not a study checkpoint"):
        p = tmp_path / "junk.json"
        p.write_text("{}")
        Study.resume(p)


def test_generic_mode_rejects_checkpointing(tmp_path):
    from repro.core.search import DiscreteSpace, FunctionEvaluator
    space = DiscreteSpace(domains={"x": (1, 2, 4)},
                          make_config=lambda **kw: kw["x"])
    study = Study(space=space, evaluator=FunctionEvaluator(float),
                  budget=SearchBudget(restarts=1, max_rounds=2))
    with pytest.raises(ValueError, match="checkpoint"):
        study.run(checkpoint_path=tmp_path / "x.ckpt")


def test_nsga2_mid_generation_checkpoint_boundary(tmp_path):
    """Engine-level checkpointing for NSGA-II on the accelerator space:
    snapshot the generation state mid-run (a round boundary inside the
    generational loop — between Study's per-app checkpoints, which only
    fall at app completion), push it through the JSON wire format, and the
    restored engine must continue bit-identically to the uninterrupted
    run."""
    from repro.core.search import Evaluator, NSGA2Optimizer

    spec = AppSpec.from_app("ptb")
    space = default_space()

    def fresh_ev():
        return Evaluator.for_space(spec.stream, space,
                                   peak_weight_bits=spec.peak_weight_bits,
                                   peak_input_bits=spec.peak_input_bits)

    def fresh_eng(ev):
        return NSGA2Optimizer(space, ev, seed=0, population=12,
                              max_rounds=5)

    def pool_dicts(pool):
        cfgs = pool.to_configs() if hasattr(pool, "to_configs") else pool
        return [c.asdict() for c in cfgs]

    ev_ref = fresh_ev()
    ref = fresh_eng(ev_ref)
    ref_pools = []
    while not ref.done:
        pool = ref.propose()
        ref_pools.append(pool_dicts(pool))
        ref.observe(pool, ev_ref(pool))

    ev_half = fresh_ev()
    half = fresh_eng(ev_half)
    for _ in range(3):                      # founding gen + 2 generations
        pool = half.propose()
        half.observe(pool, ev_half(pool))
    wire = (tmp_path / "nsga2.state.json")
    wire.write_text(json.dumps(half.state_dict()))

    ev_cont = fresh_ev()
    resumed = fresh_eng(ev_cont)
    resumed.load_state(json.loads(wire.read_text()))
    assert resumed.rounds == half.rounds
    assert resumed.best_perf == half.best_perf
    cont_pools = []
    while not resumed.done:
        pool = resumed.propose()
        cont_pools.append(pool_dicts(pool))
        resumed.observe(pool, ev_cont(pool))
    assert cont_pools == ref_pools[3:]
    assert resumed.best_perf == ref.best_perf
    assert resumed.best.asdict() == ref.best.asdict()


# ------------------------------------------------------- fault tolerance

def test_worker_raise_retries_then_succeeds(tmp_path):
    """One injected worker raise: the retry round recovers, no
    degradation, result identical to serial."""
    baseline = run_study()
    ex = ParallelExecutor(workers=2,
                          fault=FaultPlan(state_dir=str(tmp_path / "f1"),
                                          mode="raise", times=1))
    got = run_study(executor=ex)
    assert got == baseline
    assert ex.retry_rounds >= 1
    assert not ex.degraded


def test_worker_kill_breaks_pool_then_recovers(tmp_path):
    """A SIGKILLed worker poisons the whole pool (BrokenProcessPool); a
    fresh retry pool must finish the study with the exact serial result."""
    baseline = run_study()
    ex = ParallelExecutor(workers=2,
                          fault=FaultPlan(state_dir=str(tmp_path / "f2"),
                                          mode="kill", times=1,
                                          task_index=0))
    got = run_study(executor=ex)
    assert got == baseline
    assert ex.retry_rounds >= 1
    assert not ex.degraded


def test_persistent_faults_degrade_to_serial_with_warning(tmp_path):
    """When every pool round fails, the study falls back to in-process
    serial execution, warns, and still returns the correct result."""
    baseline = run_study()
    ex = ParallelExecutor(workers=2, max_retries=1,
                          fault=FaultPlan(state_dir=str(tmp_path / "f3"),
                                          mode="raise", times=999))
    with pytest.warns(ParallelExecutionWarning, match="serial"):
        got = run_study(executor=ex)
    assert got == baseline
    assert ex.degraded


# ---------------------------------------------------------- determinism

@pytest.mark.parametrize("engine", sorted(ENGINE_BUDGETS))
def test_worker_count_invariance_all_engines(engine):
    """StudyResult JSON is byte-identical at workers 1 and 2 for every
    registered engine (the full six-engine matrix — parallel fan-out is an
    execution knob, never part of the problem)."""
    kw = dict(apps=["ptb", "wdl"], engine=engine,
              budget=ENGINE_BUDGETS[engine], seed=0)
    outs = {w: result_bytes(Study(workers=w, **kw).run()) for w in (1, 2)}
    assert outs[1] == outs[2]


def test_worker_count_invariance_pareto():
    """A Pareto study — front, budget selections, meta — is byte-identical
    across workers 1, 2, 4."""
    kw = dict(apps=["ptb", "wdl"], engine="genetic",
              objective=ParetoObjective(["perf", "-area"]),
              budget=SearchBudget(restarts=1, max_rounds=4,
                                  engine_kwargs={"population": 16}),
              area_budgets=(30000.0, 60000.0, 90000.0), seed=0)
    outs = {w: result_bytes(Study(workers=w, **kw).run()) for w in (1, 2, 4)}
    assert outs[1] == outs[2] == outs[4]


@pytest.mark.parametrize("backend,workers", [("numpy", 1), ("jax", 1),
                                             ("numpy", 2)])
def test_search_log_handoff(backend, workers):
    """The evaluated log reaches synthesis as a `ConfigBatch`.  In-process
    (`workers=1`) the study hands over the live evaluator: no row becomes
    a dataclass and no cache is exported and merged.  A pool run merges
    one cache per record the pool returned.  Either way the result is the
    one a serial, obs-off run gives, and the evaluator's cache holds
    every logged row (the read-back `bench/check.py` relies on)."""
    from repro import obs
    from repro.core.costmodel import ConfigBatch
    kw = dict(apps=["ptb", "wdl"], engine="random",
              budget=ENGINE_BUDGETS["random"], seed=0, backend=backend)
    plain = result_bytes(Study(workers=1, **kw).run())
    obs.disable(reset=True)
    obs.enable(trace=False, metrics=True, journal=False)
    try:
        result = Study(workers=workers, **kw).run()
        counters = dict(obs.metrics().counters)
    finally:
        obs.disable(reset=True)
    assert result_bytes(result) == plain
    assert counters["search.rows_materialized"] == 0
    # restarts=1: the pool returns one record per app
    assert counters["study.cache_merges"] == (0 if workers == 1 else 2)
    for res in result.per_app_results.values():
        assert isinstance(res.evaluated, ConfigBatch)
        assert set(res.evaluated.row_keys()) <= \
            set(res.evaluator.cache_export())


def test_degraded_restart_chunks_fold_their_live_caches(tmp_path):
    """Restart chunks that fell back to in-process execution hand over
    live evaluators; combining them exports and folds each one's cache,
    so the rebuilt evaluator still holds every logged row and the result
    is the serial one."""
    from repro import obs
    kw = dict(apps=["ptb"], engine="random", seed=0,
              budget=SearchBudget(restarts=2, max_rounds=3,
                                  engine_kwargs={"batch": 12}))
    baseline = result_bytes(Study(**kw).run())
    ex = ParallelExecutor(workers=2, max_retries=0,
                          fault=FaultPlan(state_dir=str(tmp_path / "f"),
                                          mode="raise", times=999))
    obs.disable(reset=True)
    obs.enable(trace=False, metrics=True, journal=False)
    try:
        with pytest.warns(ParallelExecutionWarning, match="serial"):
            result = Study(executor=ex, **kw).run()
        merges = obs.metrics().counters["study.cache_merges"]
    finally:
        obs.disable(reset=True)
    assert ex.degraded
    assert result_bytes(result) == baseline
    assert merges == 2                       # two restart chunks folded
    res = result.per_app_results["ptb"]
    assert set(res.evaluated.row_keys()) <= set(res.evaluator.cache_export())


def test_resume_restores_a_batch_log(tmp_path):
    """A study resumed from its checkpoint holds the same log type as a
    fresh one, for the app decoded from the checkpoint and for the app
    searched after it, and gives the byte-identical result."""
    from repro.core.costmodel import ConfigBatch
    kw = dict(SMALL, engine="random", budget=ENGINE_BUDGETS["random"])
    baseline = result_bytes(Study(**kw).run())
    ckpt = tmp_path / "batch.ckpt"

    def boom(n):
        if n == 1:
            raise Crash

    with pytest.raises(Crash):
        Study(**kw).run(checkpoint_path=ckpt, checkpoint_every=1,
                        on_checkpoint=boom)
    resumed = Study.resume(ckpt)
    assert result_bytes(resumed) == baseline
    for res in resumed.per_app_results.values():
        assert isinstance(res.evaluated, ConfigBatch)


def test_parallel_reproduces_greedy_goldens():
    """The seed-commit greedy golden survives the process pool bit-for-bit
    (worker-side evaluator shards change nothing)."""
    res = Study(apps=["resnet"], objective=MaxPerf(), engine="greedy",
                budget=SearchBudget(k=2, restarts=2, max_rounds=6),
                seed=0, workers=2).run()
    assert {k: int(v) for k, v in res.best.asdict().items()} == GOLD_MULTI
    assert res.best_score == GOLD_MULTI_PERF


def test_parallel_reproduces_table4_selections():
    """§5.1 geomean selection (Table-4 golden) at workers=2, with the
    sharded cross-eval stage forced on: byte-identical selections."""
    study = Study(apps=["ptb", "wdl"], objective=GeomeanAcrossApps(),
                  engine="greedy",
                  budget=SearchBudget(k=2, restarts=2, max_rounds=6),
                  seed=0, workers=2)
    study.cross_eval_shard_min = 1         # force the fan-out path
    res = study.run()
    assert {k: int(v)
            for k, v in res.best.asdict().items()} == GOLD_MA_SELECTED
    assert res.multiapp_summary["geomeans"] == GOLD_MA_GEOMEANS


def test_sharded_cross_eval_matches_serial():
    """The sharded [n_apps, n_cands] cross-evaluation concatenates back to
    exactly the serial matrix."""
    space = default_space()
    specs = [AppSpec.from_app(n) for n in ("ptb", "wdl")]
    rng = np.random.default_rng(0)
    cands = [space.sample(rng) for _ in range(37)]
    serial = Study(apps=specs, space=space)._cross_eval(cands)
    par = Study(apps=specs, space=space, workers=3)
    par.cross_eval_shard_min = 1
    np.testing.assert_array_equal(par._cross_eval(cands), serial)


def test_merge_pareto_fronts_is_order_invariant():
    """Shard fronts merged in any arrival order / shard split produce one
    identical global front."""
    space = default_space()
    rng = np.random.default_rng(7)
    pool = [space.sample(rng) for _ in range(60)]
    perf = rng.uniform(10.0, 1000.0, len(pool))
    area = np.asarray([c.area(space.hw) for c in pool])
    entries = list(zip(pool, perf, area))

    def split(n_shards, seed):
        shuffled = entries[:]
        random.Random(seed).shuffle(shuffled)
        return [shuffled[i::n_shards] for i in range(n_shards)]

    ref = merge_pareto_fronts([entries])
    assert ref, "test front must be non-empty"
    for n_shards, seed in ((2, 0), (3, 1), (5, 2)):
        got = merge_pareto_fronts(split(n_shards, seed))
        assert [(e[1], e[2]) for e in got] == [(e[1], e[2]) for e in ref]
        assert [e[0].asdict() for e in got] == [e[0].asdict() for e in ref]

    # duplicated entries across shards dedupe; conflicting metrics for one
    # config are a loud error, never a silent pick
    assert merge_pareto_fronts([entries, entries]) == ref
    bad = [(pool[0], float(perf[0]) + 1.0, float(area[0]))]
    with pytest.raises(ValueError, match="conflicting"):
        merge_pareto_fronts([entries, bad])


def test_canonical_front_ties_break_by_content():
    """Metric-tied points resolve by config key, not input order."""
    perf = np.asarray([5.0, 5.0, 3.0, 0.0])
    area = np.asarray([10.0, 10.0, 4.0, 1.0])
    keys = ["b", "a", "c", "d"]
    assert canonical_front_indices(perf, area, keys) == [2, 1]
    rev = canonical_front_indices(perf[::-1].copy(), area[::-1].copy(),
                                  keys[::-1])
    assert rev == [1, 2]                   # same points under the reversal


# ------------------------------------------------------- executor (unit)

def _double(x):
    return 2 * x


def test_executor_map_orders_and_streams():
    ex = ParallelExecutor(workers=1)
    seen = []
    out = ex.map(_double, [3, 1, 2], on_result=lambda i, r: seen.append(i))
    assert out == [6, 2, 4]
    assert seen == [0, 1, 2]


def test_executor_pool_map_matches_serial():
    ex = ParallelExecutor(workers=2)
    assert ex.map(_double, list(range(8))) == [2 * i for i in range(8)]
    assert not ex.degraded


# ------------------------------------------- one device process per chip

def test_device_backend_refuses_worker_pool(monkeypatch):
    """On an accelerator a chip belongs to one process: backend="jax" with
    workers > 1 is refused before any pool is spawned, for Studies and for
    sharded evaluator scoring; the CPU backend keeps its pool."""
    import jax
    from repro.dse.parallel import EvalParams, score_population_sharded

    spawned = []
    monkeypatch.setattr(ParallelExecutor, "_pool_round",
                        lambda self, *a: spawned.append(a) or [])
    Study(apps=["ptb"], workers=2, backend="jax")      # CPU: pool allowed
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="one process"):
        Study(apps=["ptb"], workers=2, backend="jax")
    with pytest.raises(ValueError, match="one process"):
        Study(apps=["ptb"], backend="jax",
              executor=ParallelExecutor(workers=2))
    Study(apps=["ptb"], workers=1, backend="jax")
    Study(apps=["ptb"], workers=2, backend="numpy")

    spec = AppSpec.from_app("ptb")
    space = default_space()
    batch = space.decode_batch(
        space.sample_indices(np.random.default_rng(0), 8))
    params = EvalParams(stream=spec.stream, hw=space.hw, backend="jax")
    with pytest.raises(ValueError, match="one process"):
        score_population_sharded(params, batch, ParallelExecutor(workers=2))
    assert spawned == []
