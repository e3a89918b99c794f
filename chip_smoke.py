"""Chip smoke test: one `backend="jax"` DSE study on a TPU at 32B-model width.

Drives the system's main path once, in one process, through the CLI's own
study builder (`repro.dse.cli.study_from_cli`):

  a. requires a TPU (`jax.devices()[0].platform == "tpu"`); there is no
     CPU fallback;
  b. runs a geomean study over `qwen2.5-32b:prefill` and
     `qwen2.5-32b:decode` (traced at published widths) with the random
     engine at 16384 configs per round, scored on the device
     (`--backend jax`), then the same study with `--backend numpy`.
     Random proposals do not depend on scores, so both runs score
     identical pools;
  c. checks that both runs pick identical per-app bests and the identical
     geomean selection, and that every evaluated config re-scored through
     a jax and a numpy evaluator agrees: GOPS within 1e-6 relative, area
     bit-identical;
  d. prints configs scored, wall seconds per backend, XLA compiles, and
     peak device memory (informational, not metrics).

The last line of stdout is `{"ok": true, "device": {...}}`; any failure
exits nonzero without printing it.

Usage:  python chip_smoke.py
"""

from __future__ import annotations

import collections
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

APPS = ("qwen2.5-32b:prefill", "qwen2.5-32b:decode")
POOL = 16384            # configs proposed per round
ROUNDS = 3
GOPS_RTOL = 1e-6


def study_argv(backend: str) -> list:
    argv = []
    for app in APPS:
        argv += ["--apps", app]
    return argv + ["--objective", "geomean", "--engine", "random",
                   "--engine-kwarg", f"batch={POOL}",
                   "--max-rounds", str(ROUNDS), "--restarts", "1",
                   "--seed", "0", "--backend", backend]


class CompileLog:
    """XLA backend compiles (count, seconds) per jitted function name, from
    JAX's own monitoring events (persistent-cache hits included)."""

    def __init__(self):
        self.by_fun = collections.defaultdict(lambda: [0, 0.0])

    def __call__(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            rec = self.by_fun[kw.get("fun_name", "?")]
            rec[0] += 1
            rec[1] += duration

    def take(self) -> dict:
        out = {k: (n, round(s, 3)) for k, (n, s) in self.by_fun.items()}
        self.by_fun.clear()
        return out


def run_study(backend: str, compiles: CompileLog):
    from repro.dse.cli import study_from_cli
    study, _ = study_from_cli(study_argv(backend))
    t0 = time.perf_counter()
    result = study.run()
    wall = time.perf_counter() - t0
    scored = sum(len(r.evaluated) for r in result.per_app_results.values())
    print(f"[smoke] study backend={backend}: {scored} configs evaluated in "
          f"{wall:.3f} s; compiles {compiles.take()}")
    return study, result


def rescore(study, app: str, batch, backend: str):
    """Raw (GOPS, area) of a `ConfigBatch` through a fresh evaluator, in
    POOL-sized calls (one padded bucket), and the seconds spent building
    the scorer's programs per bucket (its `scorer.program` spans)."""
    from repro import obs
    from repro.core.search import Evaluator
    spec = next(s for s in study.specs if s.name == app)
    ev = Evaluator.for_space(spec.stream, study.space,
                             peak_weight_bits=spec.peak_weight_bits,
                             peak_input_bits=spec.peak_input_bits,
                             backend=backend)
    obs.enable(trace=True, metrics=False, journal=False)
    try:
        parts = [ev.score_with_area(
            batch.take(np.arange(lo, min(lo + POOL, len(batch)))))
            for lo in range(0, len(batch), POOL)]
        loads = collections.defaultdict(float)
        for e in obs.tracer().export():
            if e.get("name") == "scorer.program":
                loads[e["args"]["bucket"]] += e["dur"] / 1e6
    finally:
        obs.disable(reset=True)
    return (np.concatenate([p for p, _ in parts]),
            np.concatenate([a for _, a in parts]), dict(loads))


def main() -> int:
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"[smoke] device: {device}")
    if dev.platform != "tpu":
        print(f"[smoke] FAIL: no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1

    from repro.dse.cli import configure_compile_cache
    configure_compile_cache()
    compiles = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(compiles)

    study_j, res_j = run_study("jax", compiles)
    study_n, res_n = run_study("numpy", compiles)

    from repro.core.costmodel import ConfigBatch
    failures = []
    for app in APPS:
        if res_j.per_app[app]["best"] != res_n.per_app[app]["best"]:
            failures.append(f"{app}: best config differs")
        bj = ConfigBatch.from_configs(res_j.per_app_results[app].evaluated)
        bn = ConfigBatch.from_configs(res_n.per_app_results[app].evaluated)
        if not np.array_equal(bj.matrix, bn.matrix):
            failures.append(f"{app}: the two runs scored different pools")
            continue
        gj, aj, loads = rescore(study_j, app, bj, "jax")
        gn, an, _ = rescore(study_n, app, bn, "numpy")
        rel = np.abs(gj - gn) / np.maximum(np.abs(gn), 1e-30)
        worst = float(rel.max()) if rel.size else 0.0
        same_area = bool(np.array_equal(aj, an))
        per_bucket = {b: round(s, 3) for b, s in loads.items()}
        print(f"[smoke] {app}: {len(gj)} configs re-scored, "
              f"{int((gn > 0).sum())} feasible, max GOPS rel err "
              f"{worst:.3e}, area bit-identical {same_area}; best "
              f"{res_j.per_app[app]['best_perf']!r} GOPS (jax) vs "
              f"{res_n.per_app[app]['best_perf']!r} (numpy); scorer "
              f"program load seconds per bucket {per_bucket}")
        if not worst <= GOPS_RTOL:
            failures.append(f"{app}: GOPS rel err {worst:.3e} > {GOPS_RTOL}")
        if not same_area:
            failures.append(f"{app}: area not bit-identical")
    sel_j = res_j.multiapp_summary["selected"]
    sel_n = res_n.multiapp_summary["selected"]
    print(f"[smoke] geomean selection identical: {sel_j == sel_n}")
    if sel_j != sel_n:
        failures.append("geomean selection differs")

    stats = dev.memory_stats() or {}
    print(f"[smoke] peak device memory: "
          f"{stats.get('peak_bytes_in_use', 'not reported')} bytes")
    if failures:
        for f in failures:
            print(f"[smoke] FAIL: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
