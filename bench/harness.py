"""Seconds per `repro.dse.Study` on one chip: the benchmark's harness.

One run, in one process:

1. finds the chips the cell asks for (a TPU; no CPU fallback);
2. turns on the program's persistent compilation cache exactly as its
   entry points do (`repro.dse.cli.configure_compile_cache`);
3. traces the cell's apps, then runs one pass of the window's studies
   as warm-up: that compiles every program on the first run in a
   checkout, and loads it from the cache afterwards;
4. runs whole studies back to back for `--seconds`; each is a new
   `Study` built from the cell's files by the CLI's own flag parser
   (`repro.dse.cli.study_from_cli`) with `backend="jax"`, then `.run()`.
   Each study takes the run's seed, or, where the cell's file lists
   `study_seeds` (because the seed changes how much work a study is),
   the next of those in an order drawn from the run's seed.  The window
   holds whole passes over those seeds: a pass starts while it is open,
   and the last one runs to its end, so every run does the same work in
   another order;
5. checks the last window study of each seed against the plain
   reference (`check.py`), and every other study's outcome against the
   checked one of its seed;
6. prints the result as the last line of standard output.

Everything that belongs to one cell, configuration or metric lives in a
file of its own and is found by its name in `BENCHMARK.json`:
`bench/workloads/<cell>.json` (the study's engine and objective, every
parameter written out, the limits of the comparison),
`bench/configs/<config>.json` (apps, design space, hardware constants)
and `bench/metrics/<metric>.py` (a `read(ctx)` returning the number or
None).
"""

from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"

WINDOW_ANNOTATION = "bench.window"
SCORER_PROGRAM = "fused_jax_score"


class NoChip(RuntimeError):
    """The machine lacks the chips the cell asks for."""


# ----------------------------------------------------------- the cell
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(metric: Dict[str, Any], cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, bench: Optional[Dict[str, Any]] = None,
              root: Path = ROOT) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in bench['workloads']]}")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    return Cell(
        name=name, chips=int(entry["chips"]),
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads(
            (root / "bench" / "workloads" / f"{name}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def study_argv(cell: Cell, seed: int) -> List[str]:
    """`python -m repro.dse` flags for one study of this cell."""
    conf, t = cell.config, cell.traffic
    argv: List[str] = []
    for app in conf["apps"]:
        argv += ["--apps", app]
    argv += ["--area-budget", repr(float(conf["area_budget"])),
             "--weight-peak-mode", conf["weight_peak_mode"],
             "--objective", t["objective"], "--engine", t["engine"],
             "--k", str(t["k"]), "--restarts", str(t["restarts"]),
             "--max-rounds", str(t["max_rounds"]),
             "--top-frac", repr(float(t["top_frac"]))]
    for b in t.get("budgets", []):
        argv += ["--budgets", repr(float(b))]
    for key, val in t["engine_kwargs"].items():
        val = int(val) if isinstance(val, bool) else val
        argv += ["--engine-kwarg", f"{key}={val}"]
    return argv + ["--seed", str(int(seed)), "--backend", "jax"]


def build_study(cell: Cell, seed: int):
    from repro.dse.cli import study_from_cli
    study, _ = study_from_cli(study_argv(cell, seed))
    return study


def check_configuration(cell: Cell) -> None:
    """The program's design space and hardware constants must be the ones
    the configuration file states."""
    from repro.core.space import default_space
    space = default_space()
    conf = cell.config
    domains = {k: list(v) for k, v in space.domains.items()}
    hw = dataclasses.asdict(space.hw)
    wrong = [k for k in conf["hw"] if hw.get(k) != conf["hw"][k]]
    if domains != conf["domains"] or wrong:
        raise SystemExit(f"the program's design space differs from "
                         f"{cell.config['name']}'s file (hw keys {wrong})")


def find_chips(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"need {chips} TPU chip(s); JAX sees {len(devs)} "
                     f"{devs[0].platform!r} device(s)")
    return devs[:chips]


# ------------------------------------------------------ compile events
class CompileLog:
    """JAX's own compile events (monitoring listeners): tracing, lowering
    and backend compiles, and persistent-cache hits.  A backend compile
    that is not a cache hit is a real XLA compile."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        self.events: List[Tuple[float, str, float, str]] = []

    def on_duration(self, event: str, duration_secs: float, **kw) -> None:
        if event in (self.TRACE, self.LOWER, self.COMPILE):
            self.events.append((time.perf_counter(), event,
                                float(duration_secs),
                                str(kw.get("fun_name", "?"))))

    def on_event(self, event: str, **kw) -> None:
        if event == self.CACHE_HIT:
            self.events.append((time.perf_counter(), event, 0.0, "?"))

    def install(self) -> "CompileLog":
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self

    def between(self, t0: float, t1: float) -> List[Tuple]:
        return [e for e in self.events if t0 <= e[0] <= t1]

    @classmethod
    def summary(cls, events) -> Dict[str, float]:
        compiles = sum(1 for e in events if e[1] == cls.COMPILE)
        hits = sum(1 for e in events if e[1] == cls.CACHE_HIT)
        return {"backend_compiles": compiles, "cache_loads": hits,
                "real_compiles": compiles - hits,
                "prep_s": sum(e[2] for e in events)}


# ------------------------------------------------------------- window
@dataclasses.dataclass
class Window:
    start: float            # perf_counter at the first study's start
    wall_s: float           # first study's start to last study's end
    digests: List[Tuple[int, str]]   # (study seed, outcome) per study
    configs: int            # configurations evaluated, summed
    last: Dict[int, Tuple[Any, Any]]  # seed -> its last (study, result)
    study_s: List[float]    # wall seconds of each study, in run order

    @property
    def study(self):
        """A study of the window (they all share the cell's apps)."""
        return next(iter(self.last.values()))[0]

    @property
    def studies_differ(self) -> int:
        """Studies whose outcome differs from the last of their seed,
        the one the reference checks."""
        checked = {s: d for s, d in self.digests}
        return sum(checked[s] != d for s, d in self.digests)


def digest(result) -> str:
    blob = json.dumps(result.to_json(), sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()


def study_seeds(cell: Cell, seed: int) -> List[int]:
    """The seeds of one pass over the cell's studies, in run order."""
    fixed = cell.traffic.get("study_seeds")
    if not fixed:
        return [int(seed)]
    import numpy as np
    order = np.random.default_rng(int(seed)).permutation(len(fixed))
    return [int(fixed[i]) for i in order]


def run_studies(cell: Cell, seed: int, seconds: float) -> Window:
    """Whole passes over the cell's study seeds, back to back: a pass
    starts while fewer than `seconds` have passed, and the last one runs
    to its end."""
    seeds = study_seeds(cell, seed)
    digests: List[Tuple[int, str]] = []
    last: Dict[int, Tuple[Any, Any]] = {}
    walls: List[float] = []
    configs = 0
    start = time.perf_counter()
    while True:
        for s in seeds:
            t0 = time.perf_counter()
            study = build_study(cell, s)
            result = study.run()
            walls.append(time.perf_counter() - t0)
            digests.append((s, digest(result)))
            last[s] = (study, result)
            configs += sum(len(r.evaluated)
                           for r in result.per_app_results.values())
        if time.perf_counter() - start >= seconds:
            break
    return Window(start, time.perf_counter() - start, digests, configs,
                  last, walls)


# ------------------------------------------------------------- metrics
@dataclasses.dataclass
class Context:
    """What a metric reader may read."""
    cell: Cell
    setup_s: float
    window: Window
    compile_events: List[Tuple]
    spans: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    trace: Any = None                       # trace_reduce.Trace
    trace_window: Optional[Tuple[float, float]] = None   # ns, trace clock
    device_kind: str = ""

    @property
    def studies(self) -> int:
        return len(self.window.digests)

    def app_ops(self) -> Dict[str, int]:
        """Ops in each app's stream (the op table's width)."""
        return {s.name: len(s.stream) for s in self.window.study.specs}


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def read_metrics(entries: List[Dict[str, Any]], ctx: Context
                 ) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in entries:
        value = load_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def breakdown(ctx: Context) -> Dict[str, List]:
    """The device operations that took most time, and the device's idle
    time split by the innermost host span open over it."""
    from bench import spans as sp
    lo, hi = ctx.trace_window
    idle = ctx.trace.idle_gaps(lo, hi)
    if ctx.trace.start_epoch_ns is None:
        gaps = {"outside any span": sum(e - s for s, e in idle) / 1e3}
    else:
        t0 = ctx.trace.start_epoch_ns
        gaps = sp.attribute([((t0 + s) / 1e3, (t0 + e) / 1e3)
                             for s, e in idle], ctx.spans)
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n, v] for n, v in ctx.trace.top_ops(lo, hi)],
            "idle_gaps": [[n, v / 1e6] for n, v in top_gaps]}


# ----------------------------------------------------------------- run
def device_info(devs) -> Dict[str, Any]:
    import jax
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": max(peaks)}


def traced(cell: Cell, seed: int, seconds: float):
    """The window under the JAX profiler and `repro.obs` spans; returns
    (window, trace, spans, counters, window interval on the trace
    clock)."""
    import jax
    from repro import obs
    from bench.trace_reduce import Trace
    obs.enable(trace=True, metrics=True, journal=False)
    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(WINDOW_ANNOTATION):
                win = run_studies(cell, seed, seconds)
        finally:
            jax.profiler.stop_trace()
        trace = Trace.from_dir(log_dir)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    spans = [e for e in obs.tracer().export() if e.get("ph") == "X"]
    counters = dict(obs.metrics().counters)
    obs.disable(reset=True)
    return win, trace, spans, counters, trace.annotation(WINDOW_ANNOTATION)


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        process_start: float) -> Tuple[Dict[str, Any], List[str]]:
    """One run of the cell: (result line, check lines for stderr)."""
    from bench import check
    devs = find_chips(cell.chips)
    from repro.dse.cli import configure_compile_cache
    configure_compile_cache()
    log = CompileLog().install()
    check_configuration(cell)

    from repro.core.apps import build_app
    for app in cell.config["apps"]:
        build_app(app)
    t0 = time.perf_counter()
    for s in study_seeds(cell, seed):
        build_study(cell, s).run()
    warm = CompileLog.summary(log.between(t0, time.perf_counter()))
    setup_s = time.perf_counter() - process_start
    print(f"[bench] set-up {setup_s:.3f} s; warm-up: {warm}",
          flush=True)

    if trace:
        win, tr, spans, counters, tw = traced(cell, seed, seconds)
    else:
        win, tr, spans, counters, tw = run_studies(cell, seed, seconds), \
            None, [], {}, None
    events = log.between(win.start, win.start + win.wall_s)
    inside = CompileLog.summary(events)
    print(f"[bench] window: {len(win.digests)} studies in "
          f"{win.wall_s:.3f} s, {win.configs} configs evaluated, "
          f"{inside['real_compiles']} real XLA compiles, "
          f"{inside['cache_loads']} persistent-cache loads, "
          f"{inside['prep_s']:.3f} s tracing/lowering/compiling", flush=True)
    print(f"[bench] study seconds: {[round(w, 3) for w in win.study_s]}",
          flush=True)

    device = device_info(devs)
    ctx = Context(cell=cell, setup_s=setup_s, window=win,
                  compile_events=events, spans=spans, counters=counters,
                  trace=tr, trace_window=tw, device_kind=device["kind"])
    if trace:
        lo, hi = tw
        device["busy_s"] = tr.busy_s(lo, hi)
        device["window_s"] = (hi - lo) / 1e9
        metrics = read_metrics(cell.per_layer, ctx)
    else:
        metrics = read_metrics(cell.end_to_end, ctx)

    differ = win.studies_differ
    t0 = time.perf_counter()
    values = check.worst([
        check.compare(study, result, cell.config, cell.traffic, differ)
        for study, result in win.last.values()])
    print(f"[bench] reference check of {len(win.last)} studies: "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    correct, rows = check.judge(values, cell.traffic["limits"])
    line = {"correct": correct, "attempted": len(win.digests),
            "failed": differ, "metrics": metrics, "device": device}
    if trace:
        line["breakdown"] = breakdown(ctx)
    line["checks"] = {n: {"value": v, "limit": lim} for n, v, lim, _ in rows}
    return line, [f"check {n} {v!r} limit {lim!r} {'ok' if ok else 'FAIL'}"
                  for n, v, lim, ok in rows]


def main(argv=None, process_start: Optional[float] = None) -> int:
    import argparse
    process_start = (time.perf_counter() if process_start is None
                     else process_start)
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        line, checks = run(cell, args.seed, args.seconds, bool(args.trace),
                           process_start)
    except NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 1
    for text in checks:
        print(text, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
