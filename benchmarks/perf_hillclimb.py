"""§Perf: hypothesis -> change -> measure -> validate hillclimbs on the
three most interesting (arch x shape) pairs (baselines for all 40 cells are
in benchmarks/roofline_table.py).

Pairs (chosen from the baseline table; see EXPERIMENTS.md §Perf):
  1. qwen2.5-32b x train_4k   — flagship dense training; worst absolute gap
                                to the compute roofline (coll 33 s vs
                                comp 5.1 s), most paper-representative.
  2. olmoe-1b-7b x train_4k   — most collective-bound (coll/comp ~ 19x):
                                MoE dispatch + FSDP gathers.
  3. xlstm-1.3b x decode_32k  — collective-bound *decode* (a recurrent-state
                                layout pathology; decode should be purely
                                memory-bound).

Each pair runs the paper's multi-step greedy (k=1, memoized compiles) over
the TPU execution space (core/autotune.py), then the scripted
hypothesis-driven probes below.  Every evaluation is recorded to
experiments/autotune/<cell>/ and summarized to experiments/perf_hillclimb.json.

`--smoke` instead runs the fixed-budget engine shoot-out on the
*analytical* accelerator space (no XLA): every engine gets the same
cost-model evaluation budget on every requested app — the §5.1 CNN graphs
and the traced model-zoo workloads (`--apps zoo` / `--apps all`, see
repro.frontend) — and experiments/engine_shootout.json records best-GOPS
vs. model-call trajectories.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time
from pathlib import Path

from repro.core.autotune import CellEvaluator, ExecPoint, autotune_search

OUT = Path(__file__).resolve().parents[1] / "experiments"

# Baselines = the exact configs the 40-cell sweep used.
PAIRS = [
    {
        "arch": "qwen2.5-32b", "shape": "train_4k", "mode": "train",
        "baseline": ExecPoint(sharding_mode="fsdp", remat="full",
                              microbatches=16),
        # hypothesis-driven probes (napkin math in EXPERIMENTS.md §Perf)
        "probes": {
            "H1_tp_no_fsdp_gathers": ExecPoint(
                sharding_mode="tp", remat="full", microbatches=16),
            "H2_fewer_microbatches": ExecPoint(
                sharding_mode="fsdp", remat="full", microbatches=4),
            "H3_remat_dots": ExecPoint(
                sharding_mode="fsdp", remat="dots", microbatches=16),
            "H4_tp_mb4": ExecPoint(
                sharding_mode="tp", remat="full", microbatches=4),
        },
    },
    {
        "arch": "olmoe-1b-7b", "shape": "train_4k", "mode": "train",
        "baseline": ExecPoint(sharding_mode="fsdp", remat="full",
                              microbatches=2),
        "probes": {
            "H3_tp_params": ExecPoint(
                sharding_mode="tp", remat="full", microbatches=2),
            "H4_mb1": ExecPoint(
                sharding_mode="fsdp", remat="full", microbatches=1),
        },
    },
    {
        "arch": "xlstm-1.3b", "shape": "decode_32k", "mode": "decode",
        "baseline": ExecPoint(sharding_mode="tp", remat="none",
                              microbatches=1),
        "probes": {
            "H1_shard_mlstm_state": ExecPoint(
                sharding_mode="tp", remat="none", microbatches=1,
                extra_rules=(("mlstm_state", "model"),)),
        },
    },
]


def run(max_rounds: int = 4, verbose: bool = True,
        engines: tuple = ("greedy",)) -> dict:
    results = {}
    for pair in PAIRS:
        cell = f"{pair['arch']}_{pair['shape']}"
        ev = CellEvaluator(pair["arch"], pair["shape"], multi_pod=False)
        entry = {"baseline": None, "probes": {}, "greedy": {}}

        base_score = ev.score(pair["baseline"])
        base_rec = ev.evaluate(pair["baseline"])
        entry["baseline"] = {
            "point": dataclasses.asdict(pair["baseline"]),
            "score": base_score,
            "roofline": base_rec.get("roofline"),
        }
        if verbose:
            print(f"[{cell}] baseline score={base_score:.4f} "
                  f"(1/roofline_s)")

        for name, pt in pair["probes"].items():
            sc = ev.score(pt)
            rec = ev.evaluate(pt)
            entry["probes"][name] = {
                "point": dataclasses.asdict(pt), "score": sc,
                "roofline": rec.get("roofline"),
                "vs_baseline": (sc / base_score - 1.0) if base_score else 0.0,
            }
            if verbose:
                d = entry["probes"][name]["vs_baseline"]
                print(f"[{cell}] {name}: score={sc:.4f} ({d:+.1%})")

        entry["search"] = {}
        for engine in engines:
            log: list = []
            compiles_before = ev.n_compiles
            best_pt, best_score = autotune_search(
                ev, engine=engine, shape_mode=pair["mode"], seed=0,
                max_rounds=max_rounds, init=pair["baseline"], log=log)
            entry["search"][engine] = {
                "best_point": dataclasses.asdict(best_pt),
                "best_score": best_score,
                "vs_baseline": (best_score / base_score - 1.0)
                if base_score else 0.0,
                "n_compiles": ev.n_compiles - compiles_before,
                "log": log,
            }
            if verbose:
                print(f"[{cell}] {engine} best={best_score:.4f} "
                      f"({entry['search'][engine]['vs_baseline']:+.1%}) "
                      f"compiles={ev.n_compiles - compiles_before}")
        if "greedy" in entry["search"]:       # legacy key for older readers
            entry["greedy"] = entry["search"]["greedy"]
        results[cell] = entry

    OUT.mkdir(parents=True, exist_ok=True)
    (OUT / "perf_hillclimb.json").write_text(json.dumps(results, indent=2))
    return results


SMOKE_APPS = ("resnet", "ptb", "wdl")
SHOOTOUT_ENGINE_KW = {"k": 1, "chains": 8, "population": 24, "batch": 32,
                      "patience": 8, "max_rounds": 10 ** 6}
# rounds in a row without a fresh (uncached) model call before an engine is
# declared converged-by-cycling and cut off
SHOOTOUT_STALL_ROUNDS = 25


def _resolve_apps(app_args) -> tuple:
    """Expand --apps values: literal names, 'zoo' (all traced model-zoo
    workloads), or 'all' (seven CNN apps + the zoo)."""
    from repro.core import apps as app_registry

    out: list = []
    for a in app_args:
        if a == "all":
            out.extend(app_registry.all_app_names())
        elif a == "zoo":
            out.extend(app_registry.zoo_app_names())
        else:
            out.append(a)
    # dedupe, preserve order
    return tuple(dict.fromkeys(out))


def run_shootout(app_names: tuple = SMOKE_APPS,
                 engines: tuple = ("greedy", "anneal", "genetic", "random",
                                   "tpe", "nsga2"),
                 budget: int = 512, seed: int = 0,
                 verbose: bool = True,
                 max_rounds: int = 0,
                 out_name: str = "engine_shootout.json",
                 backend: str = "numpy",
                 weight_peak_mode: str = "streaming") -> dict:
    """Fixed-budget engine shoot-out on the analytical accelerator space.

    Every engine gets the same evaluation budget (`budget` cost-model
    calls, cache misses only) on every app — hand-built §5.1 CNN graphs
    and traced model-zoo workloads alike — and reports its best GOPS, the
    model calls it actually consumed, and the best-GOPS-vs-model-calls
    trajectory.  The budget gates *round starts* (the ask/tell contract
    requires scoring a proposed pool in full), so an engine's final round
    may overshoot by up to one pool; `model_calls` in the JSON is the
    honest per-engine count — compare trajectories at a common x rather
    than the terminal best when exact call parity matters.  No XLA
    compiles: seconds per (app, engine) pair.  Results land in
    experiments/<out_name>.

    Anytime curves ride on the `repro.obs` search journal (one record per
    ask/tell round) instead of a hand-rolled trajectory list; the raw
    journal is written next to the summary as <out_name stem>.jsonl and
    the legacy ``trajectory`` key is derived from it, so
    `plot_shootout.py` needs no changes.
    """
    import numpy as np

    from repro import obs
    from repro.core.multiapp import AppSpec
    from repro.core.search import Evaluator, make_engine
    from repro.core.space import default_space

    was_active = obs.active()
    obs.enable(trace=False, metrics=False, journal=True)
    space = default_space()
    engine_kw = dict(SHOOTOUT_ENGINE_KW)
    if max_rounds:                     # optional round bound on top of the
        engine_kw["max_rounds"] = max_rounds        # evaluation budget
    results: dict = {"budget": budget, "seed": seed, "engines": list(engines),
                     "weight_peak_mode": weight_peak_mode, "apps": {}}
    failures: list = []
    for app in app_names:
        spec = AppSpec.from_app(app, weight_peak_mode=weight_peak_mode)
        obs.set_context(app=app)
        per_engine: dict = {}
        for engine in engines:
            ev = Evaluator.for_space(spec.stream, space,
                                     peak_weight_bits=spec.peak_weight_bits,
                                     peak_input_bits=spec.peak_input_bits,
                                     backend=backend)
            eng = make_engine(engine, space, ev, seed=seed, **engine_kw)
            t0 = time.time()
            first_rec = len(obs.journal())
            n_evaluated = 0
            stall = 0
            while (not eng.done and ev.n_scored < budget
                   and stall < SHOOTOUT_STALL_ROUNDS):
                pool = eng.propose()
                if not pool:
                    break
                before = ev.n_scored
                scores = np.asarray(ev(pool), dtype=np.float64)
                eng.observe(pool, scores)
                stall = stall + 1 if ev.n_scored == before else 0
                n_evaluated += len(pool)
                best = float(eng.best_perf)
                obs.journal_record(
                    kind="round", engine=eng.name, round=int(eng.rounds),
                    pool=len(pool), n_scored=int(ev.n_scored),
                    best=(best if np.isfinite(best) else None),
                    feasible_frac=(float(np.mean(scores > 0))
                                   if scores.size else 0.0),
                    hypervolume=None)
            rounds = obs.journal().records[first_rec:]
            trajectory = [{"model_calls": int(r["n_scored"]),
                           "best_gops": float(r["best"] or 0.0)}
                          for r in rounds]
            stats = ev.stats()
            stats.pop("scored", None)   # == model_calls; one canonical key
            per_engine[engine] = {
                "best_gops": float(eng.best_perf),
                "model_calls": ev.n_scored,
                "n_evaluated": n_evaluated,
                "seconds": time.time() - t0,
                "trajectory": trajectory,
                **stats,
            }
            if verbose:
                print(f"[shootout] {app:28s} {engine:8s} "
                      f"best={eng.best_perf:10.2f} GOPS  "
                      f"model_calls={ev.n_scored:4d}/{budget}  "
                      f"t={per_engine[engine]['seconds']:.2f}s")
            if eng.best_perf <= 0:      # record, finish the sweep, fail last
                failures.append(f"{app}/{engine}")
        results["apps"][app] = per_engine

    OUT.mkdir(parents=True, exist_ok=True)
    (OUT / out_name).write_text(json.dumps(results, indent=2))
    journal_path = OUT / (Path(out_name).stem + ".jsonl")
    obs.journal().write_jsonl(journal_path)
    if not was_active:
        obs.disable(reset=True)
    if verbose:
        print(f"[shootout] wrote {OUT / out_name}")
        print(f"[shootout] wrote journal {journal_path}")
    if failures:
        raise RuntimeError(
            f"no valid (nonzero-GOPS) config found for: {failures} "
            f"(full results still written to {OUT / out_name})")
    return results


# Back-compat alias: the old CI smoke entry point is now the shoot-out.
# The old signature's third positional arg (max_rounds) keeps its meaning.
def run_smoke(engines: tuple = ("greedy", "anneal"), verbose: bool = True,
              max_rounds: int = 0, budget: int = 512) -> dict:
    return run_shootout(SMOKE_APPS, engines, budget=budget, verbose=verbose,
                        max_rounds=max_rounds)


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--engine", action="append", default=None,
                    help="search engine(s) to run (repeatable); "
                         "default: greedy (full) / all six (smoke)")
    ap.add_argument("--max-rounds", type=int, default=None,
                    help="search rounds per engine (both modes; in --smoke "
                         "it bounds rounds on top of --budget)")
    ap.add_argument("--smoke", action="store_true",
                    help="fixed-budget engine shoot-out on the analytical "
                         "space (no XLA compiles)")
    ap.add_argument("--apps", action="append", default=None,
                    help="apps for the shoot-out (repeatable): any "
                         "build_app name, 'zoo', or 'all'; default: "
                         f"{SMOKE_APPS}")
    ap.add_argument("--budget", type=int, default=512,
                    help="cost-model evaluation budget per (app, engine)")
    ap.add_argument("--backend", default="numpy", choices=("numpy", "jax"),
                    help="cost-model broadcast-kernel backend for the "
                         "shoot-out Evaluator")
    ap.add_argument("--weight-peak-mode", default="streaming",
                    choices=("strict", "streaming"),
                    help="Eq. 10/11 weight-peak reading for every app, "
                         "hand-built AND traced zoo graphs")
    args = ap.parse_args()
    if args.smoke:
        engines = tuple(args.engine
                        or ["greedy", "anneal", "genetic", "random",
                            "tpe", "nsga2"])
        run_shootout(_resolve_apps(args.apps or list(SMOKE_APPS)), engines,
                     budget=args.budget, max_rounds=args.max_rounds or 0,
                     backend=args.backend,
                     weight_peak_mode=args.weight_peak_mode)
    else:
        run(max_rounds=args.max_rounds or 4,
            engines=tuple(args.engine or ["greedy"]))
