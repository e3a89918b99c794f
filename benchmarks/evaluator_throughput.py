"""Evaluator throughput: fused hot path vs gather path vs dataclass path.

The paper's premise is that the analytical model sweeps "thousands of
candidate configurations per second" (§3); this benchmark keeps that
promise honest.  It scores the same index-array population four ways:

  legacy — the pre-PR dataclass round-trip, reproduced verbatim below:
           `SpaceCodec.decode` materializes one `AccelConfig` per point,
           cache keys are per-config `sorted(asdict())` tuples, the cost
           model rebuilds its [C, 1] columns with per-field getattr loops
           and runs the pre-PR broadcast kernel (`backend="numpy-ref"`),
           and areas are one Python `.area()` call per config.
  array  — the pre-fused `ConfigBatch` path, pinned verbatim below as
           `GatherPathEvaluator`: `decode_batch` straight from the index
           arrays, row-`tobytes()` cache keys in a Python dict loop, one
           table-driven/chunked broadcast call, vectorized `area_many`.
  fused  — the live `Evaluator`: single-pass `FusedStreamScorer`
           (validity screen on joint gather tables, Eq. 1-8 tail only on
           survivors, area folded in) behind the vectorized
           `RowHashCache`.
  jax    — the live `Evaluator` with `backend="jax"`: one jitted kernel
           per shape, shared by the process, with the evaluator's
           device-resident op tables as arguments.  Cold (first call:
           table upload, and the compile unless a program of the same
           shapes exists) and warm steady-state are reported separately;
           numpy stays the bit-exact reference.

legacy/array/fused produce bit-identical GOPS vectors (asserted every
run); jax must agree to 1e-6 relative.  Per-round scoring latency
(p50/p95 over fresh uncached pools) and a batched-vs-scalar
`repair_for_peaks` comparison ride along since both sit on the same
engine hot loop.

Results go to BENCH_evaluator.json (repo root — the committed file is the
CI baseline).  `--check <baseline.json>` exits nonzero when
  * the measured legacy->array speedup regresses to less than half the
    baseline's (machine-independent: both numbers come from one host),
  * the fused path scores below 3x the in-run gather-path `array_cps`, or
  * the warm jax path falls behind the in-run `array_cps`.

A failure of the jax backend fails the run.

Usage:
  PYTHONPATH=src python benchmarks/evaluator_throughput.py            # full
  PYTHONPATH=src python benchmarks/evaluator_throughput.py --smoke \
      --check BENCH_evaluator.json                                    # CI
  PYTHONPATH=src python benchmarks/evaluator_throughput.py --parity-zoo
"""

from __future__ import annotations

import argparse
import collections
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_io  # noqa: E402  (shared BENCH_*.json envelope I/O)

from repro.core import apps
from repro.core.costmodel import ConfigBatch, area_many, performance_gops
from repro.core.multiapp import AppSpec
from repro.core.search import Evaluator
from repro.core.space import default_space

ROOT = Path(__file__).resolve().parents[1]
DEFAULT_OUT = ROOT / "BENCH_evaluator.json"


# --------------------------------------------------------------------------
# The pre-PR dataclass evaluation path, kept verbatim as the baseline under
# measurement.  (Seed-commit `Evaluator._score_batch` + `config_key`.)
# --------------------------------------------------------------------------

def _legacy_config_key(cfg) -> tuple:
    return tuple(sorted(cfg.asdict().items()))


class LegacyEvaluator:
    """Scores a dataclass pool the way the pre-PR Evaluator did."""

    def __init__(self, stream, hw, peak_weight_bits, peak_input_bits,
                 area_budget):
        self.stream = stream
        self.hw = hw
        self.peak_weight_bits = peak_weight_bits
        self.peak_input_bits = peak_input_bits
        self.area_budget = area_budget
        self.cache: "collections.OrderedDict[tuple, tuple]" = \
            collections.OrderedDict()

    def __call__(self, pool) -> np.ndarray:
        keys = [_legacy_config_key(c) for c in pool]
        cached, fresh_seen, fresh_keys, fresh_cfgs = {}, set(), [], []
        for k, c in zip(keys, pool):
            if k in cached or k in fresh_seen:
                continue
            hit = self.cache.get(k)
            if hit is not None:
                cached[k] = hit
            else:
                fresh_seen.add(k)
                fresh_keys.append(k)
                fresh_cfgs.append(c)
        if fresh_cfgs:
            perf = performance_gops(list(fresh_cfgs), self.stream, self.hw,
                                    self.peak_weight_bits,
                                    self.peak_input_bits,
                                    backend="numpy-ref")
            areas = np.asarray([c.area(self.hw) for c in fresh_cfgs])
            if self.area_budget > 0:
                perf = np.where(areas <= self.area_budget, perf, 0.0)
            for k, pa in zip(fresh_keys, zip(perf.tolist(), areas.tolist())):
                self.cache[k] = pa
                cached[k] = pa
        return np.asarray([cached[k][0] for k in keys])


# --------------------------------------------------------------------------
# The pre-fused array evaluation path, kept verbatim as the `array` baseline
# under measurement.  (Pre-fused `Evaluator._metrics_of` + `_score_batch`:
# tobytes() row keys in a dict loop, table-driven gather/broadcast
# `performance_gops`, vectorized `area_many`.)
# --------------------------------------------------------------------------

class GatherPathEvaluator:
    """Scores a ConfigBatch pool the way the pre-fused Evaluator did."""

    def __init__(self, stream, hw, peak_weight_bits, peak_input_bits,
                 area_budget):
        self.stream = stream
        self.hw = hw
        self.peak_weight_bits = peak_weight_bits
        self.peak_input_bits = peak_input_bits
        self.area_budget = area_budget
        self.cache: "collections.OrderedDict[bytes, tuple]" = \
            collections.OrderedDict()

    def __call__(self, batch) -> np.ndarray:
        batch = ConfigBatch.from_configs(batch)
        keys = batch.row_keys()
        n = len(keys)
        perf = np.empty(n, dtype=np.float64)
        area = np.empty(n, dtype=np.float64)
        first_row, dup_rows, fresh_rows = {}, [], []
        fresh_keys = []
        for i, k in enumerate(keys):
            j = first_row.get(k)
            if j is not None:
                dup_rows.append((i, j))
                continue
            first_row[k] = i
            hit = self.cache.get(k)
            if hit is not None:
                perf[i], area[i] = hit
            else:
                fresh_keys.append(k)
                fresh_rows.append(i)
        if fresh_rows:
            rows = np.asarray(fresh_rows, dtype=np.int64)
            sub = batch.take(rows)
            fp = performance_gops(sub, self.stream, self.hw,
                                  self.peak_weight_bits,
                                  self.peak_input_bits)
            fa = area_many(sub, self.hw)
            perf[rows] = fp
            area[rows] = fa
            for k, pa in zip(fresh_keys, zip(fp.tolist(), fa.tolist())):
                self.cache[k] = pa
        for i, j in dup_rows:
            perf[i] = perf[j]
            area[i] = area[j]
        if self.area_budget > 0:
            perf = np.where(area <= self.area_budget, perf, 0.0)
        return perf


# --------------------------------------------------------------------------
# Measurement harness
# --------------------------------------------------------------------------

def _best_seconds(fn, repeats: int) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def run_bench(app: str = "resnet", pool: int = 4096, repeats: int = 5,
              seed: int = 0, verbose: bool = True) -> dict:
    spec = AppSpec.from_graph(app, apps.build_app(app))
    space = default_space()
    rng = np.random.default_rng(seed)
    idx = space.sample_indices(rng, pool)
    pw, pi = spec.peak_weight_bits, spec.peak_input_bits

    def make_ev(backend="numpy"):
        return Evaluator.for_space(spec.stream, space, peak_weight_bits=pw,
                                   peak_input_bits=pi, backend=backend)

    # ---- population scoring: ConfigBatch in, GOPS out (cold cache) ----
    # the batch is decoded once outside the timed region for both
    # array-native passes (identical work either way); the legacy pass
    # keeps its per-point decode because materializing one dataclass per
    # candidate IS the pre-PR path under measurement
    batch = space.decode_batch(idx)

    def legacy_pass():
        ev = LegacyEvaluator(spec.stream, space.hw, pw, pi,
                             space.area_budget)
        return ev(space.decode(idx))

    def array_pass():
        ev = GatherPathEvaluator(spec.stream, space.hw, pw, pi,
                                 space.area_budget)
        return ev(batch)

    def fused_pass(backend="numpy"):
        ev = make_ev(backend)
        return ev(batch)

    legacy_perf = legacy_pass()
    array_perf = array_pass()
    fused_perf = fused_pass()
    np.testing.assert_array_equal(array_perf, legacy_perf)
    np.testing.assert_array_equal(fused_perf, legacy_perf)

    t_legacy = _best_seconds(legacy_pass, repeats)
    t_array = _best_seconds(array_pass, repeats)
    t_fused = _best_seconds(fused_pass, repeats)

    # warm-cache re-score of the same population (pure hash-lookup path)
    warm_ev = make_ev()
    warm_batch = batch
    warm_ev(warm_batch)
    t_cached = _best_seconds(lambda: warm_ev(warm_batch), repeats)

    # ---- per-round latency: fresh uncached pools through ONE evaluator,
    # the shape of a live search (cache grows round over round) ----
    rounds = 16
    round_pool = max(256, pool // 8)
    round_ev = make_ev()
    round_lat = []
    for r in range(rounds):
        r_idx = space.sample_indices(rng, round_pool)
        r_batch = space.decode_batch(r_idx)
        t0 = time.perf_counter()
        round_ev(r_batch)
        round_lat.append(time.perf_counter() - t0)
    lat = np.sort(np.asarray(round_lat))
    round_p50_ms = float(np.percentile(lat, 50) * 1e3)
    round_p95_ms = float(np.percentile(lat, 95) * 1e3)

    # ---- sharded population scoring (repro.dse.parallel) ----
    # each worker scores a contiguous shard on its own evaluator shard;
    # ordered concatenation must be bit-identical to one evaluator call
    from repro.dse.parallel import (EvalParams, ParallelExecutor,
                                    score_population_sharded)
    params = EvalParams(stream=spec.stream, hw=space.hw,
                        peak_weight_bits=pw, peak_input_bits=pi,
                        area_budget=space.area_budget)
    shard_ex = ParallelExecutor(workers=2)
    sharded = score_population_sharded(params, warm_batch, shard_ex)
    np.testing.assert_array_equal(sharded, array_perf)
    t_sharded = _best_seconds(
        lambda: score_population_sharded(params, warm_batch, shard_ex),
        max(2, repeats // 2))

    # ---- batched vs scalar population repair ----
    rep_idx = idx[:min(pool, 512)]
    rep_batch = space.decode_batch(rep_idx)
    scaled_pi = pi * (int(spec.stream.batch.max()) if len(spec.stream) else 1)

    def scalar_repair():
        return [space.repair_for_peaks(c, pw, scaled_pi)
                for c in space.decode(rep_idx)]

    def batched_repair():
        return space.repair_for_peaks_many(rep_batch, pw, scaled_pi)

    np.testing.assert_array_equal(
        batched_repair().matrix,
        ConfigBatch.from_configs(scalar_repair()).matrix)
    t_rep_scalar = _best_seconds(scalar_repair, max(2, repeats // 2))
    t_rep_batch = _best_seconds(batched_repair, max(2, repeats // 2))

    results = {
        "app": app,
        "pool": pool,
        "repeats": repeats,
        "seed": seed,
        "legacy_cps": pool / t_legacy,
        "array_cps": pool / t_array,
        "fused_cps": pool / t_fused,
        "cached_cps": pool / t_cached,
        "speedup": t_legacy / t_array,
        "fused_speedup": t_array / t_fused,
        "round_pool": round_pool,
        "round_p50_ms": round_p50_ms,
        "round_p95_ms": round_p95_ms,
        "repair_pool": int(rep_idx.shape[0]),
        "repair_scalar_cps": rep_idx.shape[0] / t_rep_scalar,
        "repair_batched_cps": rep_idx.shape[0] / t_rep_batch,
        "repair_speedup": t_rep_scalar / t_rep_batch,
        # recorded, not gated: on few-core hosts the pool overhead beats
        # the win, but the parity assertion above always holds
        "sharded_workers": shard_ex.workers,
        "sharded_cps": pool / t_sharded,
    }

    # cold: a fresh evaluator's first call — jit trace + compile +
    # table upload + score (what a new (app, space) pays once)
    jax_ev = make_ev("jax")
    t0 = time.perf_counter()
    jax_perf = jax_ev(warm_batch)
    t_jax_cold = time.perf_counter() - t0
    rel = (np.abs(jax_perf - legacy_perf)
           / np.maximum(np.abs(legacy_perf), 1e-30))
    results["jax_max_rel_err"] = float(rel.max())
    # warm steady-state: the persistent jitted kernel on uncached
    # work — time the fused scorer directly (the evaluator row cache
    # would serve repeat calls as hits and measure the cache instead)
    scorer = jax_ev._scorer()
    matrix = warm_batch.matrix
    t_jax = _best_seconds(lambda: scorer.metrics(matrix), repeats)
    results["jax_cold_s"] = t_jax_cold
    results["jax_cps"] = pool / t_jax
    results["jax_speedup_vs_legacy"] = t_legacy / t_jax

    if verbose:
        print(f"[evaluator-throughput] app={app} pool={pool}")
        print(f"  legacy (dataclass) : {results['legacy_cps']:12.0f} "
              f"configs/s")
        print(f"  array  (gather)     : {results['array_cps']:12.0f} "
              f"configs/s   ({results['speedup']:.1f}x)")
        print(f"  fused  (Evaluator)  : {results['fused_cps']:12.0f} "
              f"configs/s   ({results['fused_speedup']:.1f}x vs array)")
        print(f"  warm cache          : {results['cached_cps']:12.0f} "
              f"configs/s")
        print(f"  round latency       : p50 {results['round_p50_ms']:8.2f} "
              f"ms  p95 {results['round_p95_ms']:8.2f} ms  "
              f"(pool {results['round_pool']})")
        print(f"  sharded x{results['sharded_workers']}          : "
              f"{results['sharded_cps']:12.0f} configs/s   (bit-identical)")
        print(f"  jax warm            : {results['jax_cps']:12.0f} "
              f"configs/s   (max rel err "
              f"{results['jax_max_rel_err']:.2e})")
        print(f"  jax cold (compile)  : {results['jax_cold_s']:12.3f} s "
              f"first call")
        print(f"  repair scalar       : "
              f"{results['repair_scalar_cps']:12.0f} configs/s")
        print(f"  repair batched      : "
              f"{results['repair_batched_cps']:12.0f} configs/s   "
              f"({results['repair_speedup']:.1f}x)")
    return results


def run_parity_zoo(pool: int = 256, seed: int = 0) -> float:
    """Backend parity over every traced model-zoo app.

    For each zoo app the same pool is scored through the reference
    broadcast kernel (`backend="numpy-ref"`), the fused single-pass
    scorer (the live `Evaluator`, must be bit-identical), and the jax
    backends — both the jit broadcast kernel and the fused evaluator
    path — which must agree to 1e-6 relative."""
    space = default_space()
    rng = np.random.default_rng(seed)
    worst = 0.0
    for name in apps.zoo_app_names():
        spec = AppSpec.from_graph(name, apps.build_app(name))
        batch = space.decode_batch(space.sample_indices(rng, pool))
        kw = dict(peak_weight_bits=spec.peak_weight_bits,
                  peak_input_bits=spec.peak_input_bits)
        ref = performance_gops(batch, spec.stream, space.hw,
                               backend="numpy-ref", **kw)
        # fused evaluator path: bit-identical to the reference kernel
        ev = Evaluator.for_space(spec.stream, space, **kw)
        fused_perf, fused_area = ev.score_with_area(batch)
        ref_ev = Evaluator.for_space(spec.stream, space,
                                     backend="numpy-ref", **kw)
        ref_perf, ref_area = ref_ev.score_with_area(batch)
        np.testing.assert_array_equal(fused_perf, ref_perf,
                                      err_msg=f"fused perf != ref ({name})")
        np.testing.assert_array_equal(fused_area, ref_area,
                                      err_msg=f"fused area != ref ({name})")
        rels = {}
        for label, fn, base in (
            ("jax-kernel", lambda: performance_gops(
                batch, spec.stream, space.hw, backend="jax", **kw), ref),
            # the fused jax path is compared against the budget-applied
            # reference (score_with_area masks perf over the area budget)
            ("jax-fused", lambda: Evaluator.for_space(
                spec.stream, space, backend="jax",
                **kw).score_with_area(batch)[0], ref_perf),
        ):
            jx = fn()
            rels[label] = float((np.abs(jx - base)
                                 / np.maximum(np.abs(base), 1e-30)).max())
        rel = max(rels.values())
        worst = max(worst, rel)
        status = "OK" if rel <= 1e-6 else "FAIL"
        print(f"[parity-zoo] {name:32s} fused exact  "
              f"jax rel {rels['jax-kernel']:.2e}/{rels['jax-fused']:.2e}  "
              f"{status}")
    print(f"[parity-zoo] worst over zoo: {worst:.2e}")
    if worst > 1e-6:
        raise SystemExit("jax backend diverges from numpy beyond 1e-6")
    return worst


def check_regression(results: dict, baseline: dict,
                     factor: float = 2.0,
                     fused_floor: float = 3.0) -> None:
    """Gate the run (exit 2 on failure).  Three checks, all ratios of
    numbers measured on one host within one run — they transfer across
    machines where absolute configs/sec do not:

      * legacy->array speedup must not regress > `factor`x vs the
        committed baseline (pool sizes must match for the ratio to be
        comparable; --smoke keeps the baseline's pool for this reason),
      * fused_cps must be >= `fused_floor` x the in-run array_cps (the
        fused hot path earns its complexity or fails loudly),
      * warm jax_cps must be >= the in-run array_cps (the accelerator
        backend at least matches the numpy gather path).
    """
    # -- in-run gates (no baseline dependence) --
    array_cps = float(results.get("array_cps", 0.0))
    fused_cps = float(results.get("fused_cps", 0.0))
    if array_cps > 0 and fused_cps < fused_floor * array_cps:
        print(f"[check] REGRESSION: fused {fused_cps:.0f} configs/s < "
              f"{fused_floor:g}x array {array_cps:.0f} configs/s")
        raise SystemExit(2)
    print(f"[check] ok: fused {fused_cps / max(array_cps, 1e-30):.1f}x "
          f"array (gate: >= {fused_floor:g}x)")
    jax_cps = float(results["jax_cps"])
    if jax_cps < array_cps:
        print(f"[check] REGRESSION: warm jax {jax_cps:.0f} configs/s < "
              f"array {array_cps:.0f} configs/s")
        raise SystemExit(2)
    print(f"[check] ok: warm jax {jax_cps / max(array_cps, 1e-30):.1f}x "
          f"array (gate: >= 1x)")
    # -- baseline gate --
    base_speedup = float(baseline.get("speedup", 0.0))
    if int(results.get("pool", 0)) != int(baseline.get("pool", 0)):
        print(f"[check] pool mismatch (baseline "
              f"{baseline.get('pool')}, got {results.get('pool')}); "
              "skipping the speedup gate")
        return
    got = float(results["speedup"])
    if base_speedup > 0 and got < base_speedup / factor:
        print(f"[check] REGRESSION: speedup {got:.1f}x < baseline "
              f"{base_speedup:.1f}x / {factor:g}")
        raise SystemExit(2)
    print(f"[check] ok: speedup {got:.1f}x vs baseline "
          f"{base_speedup:.1f}x (gate: >= {base_speedup / factor:.1f}x)")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--app", default="resnet",
                    help="workload to score (any build_app name)")
    ap.add_argument("--pool", type=int, default=4096,
                    help="population size per scoring pass")
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: smaller pool, fewer repeats")
    ap.add_argument("--out", type=Path, default=DEFAULT_OUT,
                    help=f"JSON output path (default {DEFAULT_OUT})")
    ap.add_argument("--check", type=Path, default=None,
                    help="baseline JSON to gate against (>2x speedup "
                         "regression fails); read before --out overwrites")
    ap.add_argument("--parity-zoo", action="store_true",
                    help="check numpy-vs-jax parity on every zoo app "
                         "instead of benchmarking")
    args = ap.parse_args()

    if args.parity_zoo:
        run_parity_zoo()
        sys.exit(0)

    if args.smoke:
        # keep the baseline's pool size (the speedup ratio shifts with pool
        # because fixed overheads amortize differently — the gate must
        # compare like-for-like); just cap the repeats.  ~5 s total.
        args.repeats = min(args.repeats, 5)

    # read the committed baseline BEFORE --out (possibly the same file)
    # overwrites it; read_results accepts the legacy flat layout too
    baseline = (bench_io.read_results(args.check)
                if args.check and args.check.exists() else None)
    results = run_bench(app=args.app, pool=args.pool, repeats=args.repeats)
    results["smoke"] = bool(args.smoke)
    bench_io.write_results(args.out, "evaluator_throughput", results)
    print(f"[evaluator-throughput] wrote {args.out}")
    if args.check is not None:
        if baseline is None:
            print(f"[check] no baseline at {args.check}; skipping gate")
        else:
            check_regression(results, baseline)
