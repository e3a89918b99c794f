"""Software-defined DSE for the TPU execution space (beyond-paper layer).

The paper's framework = {application graph} x {analytical cost model} x
{multi-step greedy optimizer}.  Here the *same* optimizer drives the TPU
execution design space:

  paper variable        ->  TPU execution variable
  ----------------------------------------------------------------
  PE organisation       ->  sharding_mode (fsdp | tp)
  loop tiling T*        ->  microbatches, attn_kv_block
  banked buffers        ->  remat policy (activation residency)
  loop_order            ->  kv cache layout axis (model | data)

and the cost model is the compiled-artifact roofline (core/roofline.py):
score = 1 / max(compute_s, memory_s, collective_s), with the paper's
"0 GOPS on constraint violation" rule mapped to peak_bytes > HBM.

Because one evaluation = one XLA compile (~10-60 s on this host), the
greedy runs with k=1 and persistent on-disk memoization — the same
Algorithm 1 semantics at the affordable pool size (the paper itself notes
k trades optimality for search cost).

`select_geomean_config` reproduces the paper's §5.1 multi-application
study on this space: one execution configuration chosen by geometric-mean
roofline across all ten assigned architectures.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.roofline import HW, RooflineReport
from repro.core.search import (DiscreteSpace, EngineSpec, FunctionEvaluator,
                               filter_kwargs)

__all__ = ["ExecPoint", "EXEC_DOMAINS", "CellEvaluator", "exec_space",
           "greedy_autotune", "autotune_search", "select_geomean_config"]


@dataclasses.dataclass(frozen=True)
class ExecPoint:
    """One point in the TPU execution design space."""

    sharding_mode: str = "fsdp"        # fsdp | tp
    remat: str = "full"                # full | dots | none
    microbatches: int = 1              # gradient accumulation factor
    attn_kv_block: int = 1024          # online-softmax KV tile
    extra_rules: Tuple[Tuple[str, Optional[str]], ...] = ()

    def key(self) -> str:
        return hashlib.sha1(json.dumps(
            dataclasses.asdict(self), sort_keys=True).encode()).hexdigest()[:12]

    def overrides(self) -> Dict[str, Any]:
        return {"attn_kv_block": self.attn_kv_block}


EXEC_DOMAINS: Dict[str, Tuple] = {
    "sharding_mode": ("fsdp", "tp"),
    "remat": ("full", "dots", "none"),
    "microbatches": (1, 2, 4, 8, 16),
    "attn_kv_block": (512, 1024, 2048, 4096),
    # cache/state layout flips (the paper's loop_order analogue)
    "extra_rules": ((), (("mlstm_state", "model"),),
                    (("kv_seq", None),)),
}


class CellEvaluator:
    """Compile-and-score one (arch x shape x mesh) cell at an ExecPoint,
    with on-disk memoization (evaluations are expensive)."""

    def __init__(self, arch_name: str, shape_name: str, multi_pod: bool,
                 cache_dir: str = "experiments/autotune",
                 hbm_limit: float = 16e9, compile_workers: int = 1):
        self.arch_name = arch_name
        self.shape_name = shape_name
        self.multi_pod = multi_pod
        mesh_name = "2x16x16" if multi_pod else "16x16"
        self.cell = f"{arch_name}_{shape_name}_{mesh_name}"
        self.dir = Path(cache_dir) / self.cell
        self.dir.mkdir(parents=True, exist_ok=True)
        self.hbm_limit = hbm_limit
        self.compile_workers = max(1, int(compile_workers))
        self.n_compiles = 0

    def evaluate(self, pt: ExecPoint) -> Dict[str, Any]:
        cache = self.dir / f"{pt.key()}.json"
        if cache.exists():
            return json.loads(cache.read_text())
        from repro.launch.dryrun import run_cell
        rec = run_cell(self.arch_name, self.shape_name, self.multi_pod,
                       self.dir, sharding_mode=pt.sharding_mode,
                       remat=pt.remat, microbatches=pt.microbatches,
                       overrides=pt.overrides(),
                       rule_updates=dict(pt.extra_rules) or None,
                       tag=f"_{pt.key()}")
        self.n_compiles += 1
        rec["point"] = dataclasses.asdict(pt)
        cache.write_text(json.dumps(rec, indent=2))
        return rec

    def score(self, pt: ExecPoint) -> float:
        """1/roofline_s; 0 on failure or HBM violation (paper's 0-GOPS)."""
        rec = self.evaluate(pt)
        if rec.get("status") != "OK":
            return 0.0
        roof = rec["roofline"]
        if roof["peak_memory_per_chip"] > self.hbm_limit:
            return 0.0
        return 1.0 / max(roof["roofline_s"], 1e-12)

    def score_batch(self, pts: Sequence[ExecPoint]) -> List[float]:
        """Score a pool, overlapping compiles on `compile_workers` threads
        (each evaluation is an external XLA compile, so threads overlap
        fine; per-point cache files are distinct).  Results come back in
        pool order, so engines see exactly the serial scores."""
        pts = list(pts)
        if self.compile_workers <= 1 or len(pts) <= 1:
            return [self.score(p) for p in pts]
        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(
                max_workers=min(self.compile_workers, len(pts))) as tp:
            return list(tp.map(self.score, pts))


def _domains_for(shape_mode: str) -> Dict[str, Tuple]:
    d = dict(EXEC_DOMAINS)
    if shape_mode != "train":
        d["microbatches"] = (1,)
        d["remat"] = ("none",)
        d["sharding_mode"] = ("tp",)
    return d


def exec_space(shape_mode: str = "train") -> DiscreteSpace:
    """The TPU execution design space as a generic `DiscreteSpace`, so any
    search engine ("anneal", "genetic", "random", ...) can drive it."""
    return DiscreteSpace(domains=_domains_for(shape_mode),
                         make_config=lambda **kw: ExecPoint(**kw))


def autotune_search(evaluator: CellEvaluator, *, engine: EngineSpec = "greedy",
                    shape_mode: str = "train", seed: int = 0,
                    max_rounds: int = 6,
                    init: Optional[ExecPoint] = None,
                    log: Optional[list] = None,
                    **engine_kwargs) -> Tuple[ExecPoint, float]:
    """Engine-pluggable autotuning of one cell.

    "greedy" keeps the k=1 memoized-compile loop below (its budget model is
    tuned for ~10-60 s evaluations); other engines run through the generic
    driver with deliberately small population defaults — every scored point
    is one XLA compile, memoized by `CellEvaluator` on disk and by
    `FunctionEvaluator` in memory.
    """
    if engine == "greedy":
        # same superset-tolerant kwarg handling make_engine gives the other
        # engines: forward only what greedy_autotune understands
        return greedy_autotune(evaluator, shape_mode=shape_mode, seed=seed,
                               max_rounds=max_rounds, init=init, log=log,
                               **filter_kwargs(greedy_autotune,
                                               engine_kwargs))
    from repro.dse import SearchBudget, Study

    space = exec_space(shape_mode)
    # cache misses of each pool flow through score_batch in one call, so a
    # CellEvaluator(compile_workers=N) overlaps its expensive compiles;
    # score-only evaluators (duck-typed) fall back to the scalar path
    fev = FunctionEvaluator(evaluator.score,
                            batch_score_fn=getattr(evaluator, "score_batch",
                                                   None))
    kw: Dict[str, Any] = {"chains": 2, "population": 6, "batch": 4,
                          "elite": 1}
    kw.update(engine_kwargs)
    if init is not None:
        kw.setdefault("init", init)
    # evaluator-driven (generic) Study: one engine run over the execution
    # space through the declarative front door — same make_engine kwarg
    # filtering, same seed, same ask/tell loop as before
    study = Study(space=space, evaluator=fev, engine=engine,
                  budget=SearchBudget(restarts=1, max_rounds=max_rounds,
                                      engine_kwargs=kw),
                  seed=seed, name="autotune")
    res = study.run().per_app_results["space"]
    best, best_perf = res.best, res.best_perf
    if init is not None:
        # engines without an `init` parameter (genetic, random) drop it in
        # make_engine's kwarg filtering — score it explicitly so the
        # starting point is always a candidate (memoized: free if an
        # init-seeded engine already scored it)
        init_score = fev.score_one(init)
        if best is None or init_score > best_perf:
            best, best_perf = init, init_score
    if best is None:
        raise ValueError(
            f"{engine} search evaluated no candidates (max_rounds="
            f"{max_rounds}); use max_rounds >= 1 or pass init=")
    if log is not None:
        log.append({"event": "search", "engine": res.engine,
                    "rounds": res.rounds,
                    "evaluated": [dataclasses.asdict(c)
                                  for c in res.evaluated],
                    "scores": res.evaluated_perf.tolist(),
                    "best": dataclasses.asdict(best)})
    return best, best_perf


def greedy_autotune(evaluator: CellEvaluator, *, shape_mode: str = "train",
                    seed: int = 0, max_rounds: int = 6,
                    init: Optional[ExecPoint] = None,
                    delta_threshold: float = 0.02,
                    log: Optional[list] = None) -> Tuple[ExecPoint, float]:
    """Algorithm 1 with k=1 over the execution space (memoized evals)."""
    rng = np.random.default_rng(seed)
    domains = _domains_for(shape_mode)
    s0 = init or ExecPoint()
    p0 = evaluator.score(s0)
    if log is not None:
        log.append({"event": "init", "point": dataclasses.asdict(s0),
                    "score": p0})
    variables = list(domains.keys())
    stale = 0
    for rnd in range(max_rounds):
        var = variables[int(rng.integers(len(variables)))]
        pool = [s0]
        for v in domains[var]:
            pool.append(dataclasses.replace(s0, **{var: v}))
        scores = [evaluator.score(s) for s in pool]
        i_max = int(np.argmax(scores))
        delta = scores[i_max] - p0
        if log is not None:
            log.append({"event": "round", "var": var,
                        "candidates": [dataclasses.asdict(s) for s in pool],
                        "scores": scores,
                        "picked": dataclasses.asdict(pool[i_max])})
        s0, p0 = pool[i_max], scores[i_max]
        if delta <= delta_threshold * max(p0, 1e-12):
            stale += 1
            if stale >= 2:
                break
        else:
            stale = 0
    return s0, p0


def select_geomean_config(records: Dict[str, Dict[str, float]]
                          ) -> Tuple[str, float]:
    """§5.1 selection on the TPU space: records[point_key][arch] = score;
    returns the point key with the best geometric-mean score over archs
    (points missing an arch or scoring 0 anywhere are excluded)."""
    best_key, best_geo = "", 0.0
    n_archs = max(len(v) for v in records.values())
    for key, per_arch in records.items():
        vals = list(per_arch.values())
        if len(vals) < n_archs or any(v <= 0 for v in vals):
            continue
        geo = float(np.exp(np.mean(np.log(vals))))
        if geo > best_geo:
            best_key, best_geo = key, geo
    return best_key, best_geo
