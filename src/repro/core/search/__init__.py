"""Pluggable batched search engines for design-space exploration.

The paper casts accelerator design as a multi-dimensional optimization
problem solved by a search loop over an analytical cost model (§4.3,
Algorithm 1).  This package makes the *search strategy* a pluggable
component so every consumer (`multiapp.py`, `sensitivity.py`,
`autotune.py`, the benchmarks and examples) can swap engines by name.

The Optimizer interface
=======================

Every engine is an ask/tell `Optimizer` (see `base.py`)::

    class Optimizer:
        def propose(self) -> List[config]:
            '''Next pool of candidates to score (may be empty to stop).'''
        def observe(self, pool, scores: np.ndarray) -> None:
            '''Scores for the pool just proposed; update internal state.'''
        @property
        def done(self) -> bool:
            '''True once converged / budget exhausted.'''

plus bookkeeping attributes maintained by the engine as it observes:
``best``, ``best_perf``, ``history`` (per-round incumbent) and ``rounds``.

The driver is deliberately dumb::

    while not engine.done:
        pool = engine.propose()
        scores = evaluator(pool)        # ONE batched cost-model call
        engine.observe(pool, scores)

`run_search(engine, evaluator)` implements exactly this loop and returns a
`SearchResult` (best / history / every evaluated config + score — the
top-10 % candidate selection of §5.1 consumes the full log; on the
accelerator space that log is one `ConfigBatch`).

The shared Evaluator
====================

`Evaluator` (see `evaluator.py`) scores candidate pools through the fused
single-pass cost model (`FusedStreamScorer`, bit-identical to the
`evaluate_stream_many` reference + `area_many`) and memoizes in a vectorized
open-addressed row cache (`rowcache.RowHashCache`: 64-bit row hashes,
exact-key collision fallback, LRU eviction), so repeated points — across
rounds, restarts, and even different engines sharing one evaluator — are
never re-scored and cache probing costs a handful of array ops per pool.
Pools are **array-native**: engines on the accelerator space propose
`ConfigBatch` struct-of-arrays populations (built straight from
`SpaceCodec` index arrays via `DesignSpace.decode_batch`,
validity-repaired in bulk by `repair_for_peaks_many`) — no dataclass is
materialized on the scoring hot path, and `run_search` journals how many
proposals each round repeats from earlier rounds (`dedup_skipped`).
`FunctionEvaluator` gives the same pool interface over an arbitrary scalar
scorer (e.g. compile-and-measure cells in `core/autotune.py`); pass
`batch_score_fn` to score each pool's cache-miss set in one call.

Engines
=======

============  ==========================================================
``greedy``    Multi-step greedy, Algorithm 1 verbatim (bit-for-bit port
              of the original `multi_step_greedy`).
``anneal``    Simulated annealing: `chains` parallel Metropolis walkers,
              single-variable moves, geometric cooling.
``genetic``   Evolutionary search over the power-of-two domains:
              tournament selection, uniform crossover, random-reset
              mutation, elitism; population kept as a struct-of-arrays
              index matrix (`SpaceCodec`).
``random``    Uniform random draws (validity-repaired) — the baseline.
``tpe``       Tree-structured Parzen Estimator: per-dimension smoothed
              categorical densities over the codec index columns, good/
              bad split at the `gamma` quantile, batched candidates
              ranked by EI ratio — the surrogate-guided engine for
              expensive evaluators.
``nsga2``     NSGA-II: fast non-dominated sort + crowding distance over
              the raw [N, M] objective rows (constraint-domination via
              the feasibility mask), (mu + lambda) elitism, offspring
              repaired in bulk — the native multi-objective engine.
============  ==========================================================

Multi-objective mode
====================

Any `SearchResult` exposes `pareto_front()` — the non-dominated
(GOPS up, area down) subset of every config the run evaluated — so a
perf/area trade-off curve costs nothing beyond the search itself.

Typical use::

    from repro.core.search import optimize_for_app
    res = optimize_for_app(stream, space, engine="genetic", seed=0)
    print(res.best, res.best_perf)
    for pt in res.pareto_front():
        print(pt.perf, pt.area)
"""

from __future__ import annotations

import inspect
from typing import Any, Callable, Dict, List, Optional, Union

import numpy as np

from repro.core.costmodel import ConfigBatch
from repro.core.search.base import (DiscreteSpace, Optimizer, ParetoPoint,
                                    SearchResult, SpaceCodec,
                                    pack_config, pareto_front_indices,
                                    repair_many_with, repair_with,
                                    run_search, unpack_config)
from repro.core.search.evaluator import (Evaluator, FunctionEvaluator,
                                         config_key)
from repro.core.search.partition import (Partition, enumerate_assignments,
                                         enumerate_partitions,
                                         enumerate_splits, group_members,
                                         tier_shares)
from repro.core.search.rowcache import (RowHashCache, first_occurrence,
                                        hash_rows)
from repro.core.search.greedy import GreedyOptimizer
from repro.core.search.anneal import AnnealOptimizer
from repro.core.search.genetic import GeneticOptimizer
from repro.core.search.random_search import RandomSearchOptimizer
from repro.core.search.tpe import TPEOptimizer
from repro.core.search.nsga2 import NSGA2Optimizer

__all__ = [
    "Optimizer", "SearchResult", "ParetoPoint", "run_search",
    "SpaceCodec", "DiscreteSpace", "pareto_front_indices",
    "ConfigBatch", "repair_with", "repair_many_with",
    "pack_config", "unpack_config",
    "Evaluator", "FunctionEvaluator", "config_key",
    "RowHashCache", "first_occurrence", "hash_rows",
    "Partition", "enumerate_assignments", "enumerate_splits",
    "enumerate_partitions", "tier_shares", "group_members",
    "GreedyOptimizer", "AnnealOptimizer", "GeneticOptimizer",
    "RandomSearchOptimizer", "TPEOptimizer", "NSGA2Optimizer",
    "ENGINES", "EngineSpec", "filter_kwargs", "make_engine",
    "optimize_for_app", "multi_step_greedy",
]

ENGINES: Dict[str, type] = {
    "greedy": GreedyOptimizer,
    "anneal": AnnealOptimizer,
    "genetic": GeneticOptimizer,
    "random": RandomSearchOptimizer,
    "tpe": TPEOptimizer,
    "nsga2": NSGA2Optimizer,
}

EngineSpec = Union[str, Callable[..., Optimizer]]


def filter_kwargs(fn: Callable, kwargs: Dict[str, Any]) -> Dict[str, Any]:
    """Drop keyword arguments `fn` does not accept (superset tolerance:
    callers may pass a union of every engine's knobs; each callee takes
    what it understands).  No-op if `fn` takes **kwargs."""
    params = inspect.signature(fn).parameters
    if any(p.kind == inspect.Parameter.VAR_KEYWORD for p in params.values()):
        return dict(kwargs)
    return {k: v for k, v in kwargs.items() if k in params}


def make_engine(engine: EngineSpec, space, evaluator, **kwargs) -> Optimizer:
    """Instantiate an engine from a name or factory.

    Keyword arguments the engine's constructor does not accept are dropped
    (`filter_kwargs`), so callers can pass a superset (e.g. greedy's
    `k`/`patience` alongside genetic's `population`) and each engine takes
    what it understands.
    """
    if isinstance(engine, str):
        if engine not in ENGINES:
            raise ValueError(f"unknown engine {engine!r}; available: "
                             f"{sorted(ENGINES)}")
        factory = ENGINES[engine]
    else:
        factory = engine
    eng = factory(space, evaluator, **filter_kwargs(factory, kwargs))
    # vector-objective evaluators (repro.dse ParetoObjective) expose a
    # scalarize hook; install it so engines reduce [N, M] rows themselves
    # when driven outside run_search (e.g. the shoot-out loop)
    if getattr(eng, "scalarizer", None) is None:
        obj = getattr(evaluator, "objective", None)
        if obj is not None and hasattr(obj, "scalarize"):
            eng.scalarizer = evaluator.scalarize
    return eng


def optimize_for_app(
    stream,
    space,
    k: int = 3,
    restarts: int = 4,
    seed: int = 0,
    peak_weight_bits: int = 0,
    peak_input_bits: int = 0,
    max_rounds: int = 40,
    engine: EngineSpec = "greedy",
    engine_kwargs: Optional[Dict[str, Any]] = None,
    evaluator: Optional[Evaluator] = None,
) -> SearchResult:
    """Multi-start wrapper: the paper restarts from random initial points to
    avoid local optima; we merge the evaluated sets so top-10 % candidate
    selection (§5.1) sees every scored configuration.

    One `Evaluator` (and hence one LRU cache) is shared across all
    restarts, so configurations revisited by different starts are scored
    exactly once.  With the default `engine="greedy"` this reproduces the
    pre-refactor `repro.core.greedy.optimize_for_app` bit-for-bit.
    """
    if evaluator is None:
        evaluator = Evaluator.for_space(stream, space,
                                        peak_weight_bits=peak_weight_bits,
                                        peak_input_bits=peak_input_bits)
    kw: Dict[str, Any] = {"k": k, "patience": 3, "max_rounds": max_rounds}
    kw.update(engine_kwargs or {})
    seed = kw.pop("seed", seed)       # engine_kwargs may override the base
    # restart results reduce through the canonical SearchResult.merge
    # (earliest-max incumbent, logs concatenated in restart order) — the
    # same deterministic reduce the parallel execution layer uses for
    # worker shards, so serial and fanned-out runs agree bit-for-bit
    results: List[SearchResult] = []
    for r in range(restarts):
        eng = make_engine(engine, space, evaluator,
                          seed=seed + 1000 * r, **kw)
        results.append(run_search(eng, evaluator))
    return SearchResult.merge(results, evaluator=evaluator)


def multi_step_greedy(
    stream,
    space,
    k: int = 3,
    delta_p_threshold: float = 1e-3,
    max_rounds: int = 40,
    seed: int = 0,
    init: Optional[Any] = None,
    peak_weight_bits: int = 0,
    peak_input_bits: int = 0,
    pool_cap: int = 20000,
    patience: int = 1,
) -> SearchResult:
    """Algorithm 1, single start (paper §4.3).  `k` trades off optimality
    and per-round cost.  Formerly `repro.core.greedy.multi_step_greedy`
    (that shim has since been removed); reproduces the pre-refactor
    results bit-for-bit on a fixed seed."""
    evaluator = Evaluator.for_space(stream, space,
                                    peak_weight_bits=peak_weight_bits,
                                    peak_input_bits=peak_input_bits)
    engine = GreedyOptimizer(space, evaluator, k=k,
                             delta_p_threshold=delta_p_threshold,
                             max_rounds=max_rounds, seed=seed, init=init,
                             pool_cap=pool_cap, patience=patience)
    return run_search(engine, evaluator)
