"""repro.obs: telemetry is result-inert across every engine and worker
count, worker trace buffers merge onto the parent timeline with their own
pids, the search journal validates against its schema, per-op attribution
agrees bit-for-bit with the cost model, and the shared bench I/O envelope
round-trips (including legacy flat baselines)."""

import json
import logging
import warnings

import numpy as np
import pytest

from repro import obs
from repro.core.costmodel import evaluate_stream
from repro.core.multiapp import AppSpec
from repro.core.space import default_space
from repro.dse import ParallelExecutionWarning, ParallelExecutor, \
    SearchBudget, Study
from test_parallel_study import ENGINE_BUDGETS

SMALL = dict(apps=["ptb", "wdl"], engine="greedy",
             budget=SearchBudget(k=2, restarts=1, max_rounds=3), seed=0)


@pytest.fixture(autouse=True)
def obs_reset():
    """Every test starts and ends with obs fully off and empty — module
    state must never leak between tests (or into the rest of the suite)."""
    obs.disable(reset=True)
    yield
    obs.disable(reset=True)


def result_bytes(result) -> str:
    return json.dumps(result.to_json(), sort_keys=True)


def run_study(**overrides):
    kw = dict(SMALL)
    kw.update(overrides)
    return Study(**kw).run()


# ---------------------------------------------------------- result-inertness

@pytest.mark.parametrize("engine", sorted(ENGINE_BUDGETS))
@pytest.mark.parametrize("workers", [1, 2])
def test_telemetry_is_result_inert(engine, workers):
    """The acceptance contract: StudyResult JSON is byte-identical with
    all three obs pillars on vs. everything off, for every registered
    engine at workers 1 and 2."""
    kw = dict(apps=["ptb", "wdl"], engine=engine,
              budget=ENGINE_BUDGETS[engine], seed=0, workers=workers)
    plain = result_bytes(Study(**kw).run())

    obs.enable(trace=True, metrics=True, journal=True)
    traced_result = Study(**kw).run()
    traced = result_bytes(traced_result)
    obs.disable(reset=True)

    assert traced == plain
    # telemetry rides in meta at runtime but never in the persisted JSON
    assert "telemetry" in traced_result.meta
    assert "telemetry" not in traced_result.to_json()["meta"]


def test_telemetry_snapshot_contents():
    obs.enable(trace=True, metrics=True, journal=True)
    result = run_study(workers=2)
    tel = result.meta["telemetry"]
    assert tel["configs_scored"] > 0
    assert tel["wall_seconds"] > 0
    assert set(tel["per_app"]) == {"ptb", "wdl"}
    assert tel["executor"]["workers"] == 2
    assert tel["journal_records"] > 0
    assert tel["trace_events"] > 0
    counters = tel["metrics"]["counters"]
    assert counters.get("evaluator.scored", 0) > 0
    assert counters.get("evaluator.cache_misses", 0) > 0


def test_restart_chunking_is_worker_invariant():
    """One app, restarts > 1: extra workers split the restarts into
    chunks; the merged record must be byte-identical to serial."""
    kw = dict(apps=["resnet"], engine="tpe",
              budget=SearchBudget(restarts=4, max_rounds=3,
                                  engine_kwargs={"batch": 8,
                                                 "startup_rounds": 1}),
              seed=0)
    outs = {w: result_bytes(Study(workers=w, **kw).run())
            for w in (1, 2, 3)}
    assert outs[1] == outs[2] == outs[3]


# -------------------------------------------------------------- trace merge

def test_worker_spans_merge_with_distinct_pids(tmp_path):
    """At workers=2 the merged trace carries spans from the parent AND
    from spawned worker pids, each labeled by an "M" process_name event,
    and worker spans sit inside the parent study span on the shared
    epoch-µs timeline."""
    obs.enable(trace=True, metrics=False, journal=False)
    run_study(workers=2)
    trace = obs.tracer().chrome_trace()
    obs.disable()  # keep the buffer for inspection

    events = trace["traceEvents"]
    spans = [e for e in events if e["ph"] == "X"]
    study = [e for e in spans if e["name"] == "study"]
    assert len(study) == 1
    study_pid = study[0]["pid"]
    worker_spans = [e for e in spans
                    if e["name"] == "search_app" and e["pid"] != study_pid]
    assert worker_spans, "no spans from worker processes were merged"
    t0, t1 = study[0]["ts"], study[0]["ts"] + study[0]["dur"]
    for ev in worker_spans:
        assert t0 <= ev["ts"] and ev["ts"] + ev["dur"] <= t1 + 1000, \
            "worker span must nest (epoch-µs) inside the parent study span"
    meta_pids = {e["pid"] for e in events if e["ph"] == "M"}
    assert study_pid in meta_pids
    assert all(ev["pid"] in meta_pids for ev in worker_spans), \
        "every worker pid needs its process_name metadata event"

    from repro.obs.validate import validate_chrome_trace
    path = tmp_path / "trace.json"
    obs.tracer().write(path)
    validate_chrome_trace(path, expect_processes=2)


def test_serial_run_traces_in_process():
    obs.enable(trace=True, metrics=False, journal=False)
    run_study(workers=1)
    names = {e["name"] for e in obs.tracer().export() if e.get("ph") == "X"}
    assert {"study", "phase.search", "search_app",
            "ask_tell_round", "evaluate_batch"} <= names


def test_disabled_obs_records_nothing():
    run_study(workers=2)
    assert len(obs.tracer()) == 0
    assert len(obs.journal()) == 0
    exp = obs.metrics().export()
    assert exp["counters"] == {} and exp["histograms"] == {}


def test_counters_and_gauges_wait_for_enable():
    """Counts made while metrics are off (a warm-up pass) never reach a
    later export; counting starts at `enable`."""
    obs.counter("scorer.programs", 3)
    obs.gauge("pool.workers", 2)
    assert obs.metrics().export()["counters"] == {}
    assert obs.metrics().export()["gauges"] == {}
    obs.enable(trace=False, metrics=True, journal=False)
    obs.counter("scorer.programs")
    obs.counter("scorer.programs", 2)
    obs.gauge("pool.workers", 4)
    exp = obs.metrics().export()
    assert exp["counters"] == {"scorer.programs": 3}
    assert exp["gauges"] == {"pool.workers": 4.0}


def _within(outers, inner) -> bool:
    return any(o["ts"] <= inner["ts"] and inner["ts"] + inner["dur"]
               <= o["ts"] + o["dur"] for o in outers)


def test_device_study_spans_nest_at_their_layer():
    """A `backend="jax"` study records every span and counter of the
    scorer, the engine rounds and result materialisation, each inside
    the layer above it, and its result JSON is the one obs-off gives."""
    kw = dict(apps=["ptb", "wdl"], engine="random", backend="jax",
              budget=ENGINE_BUDGETS["random"], seed=0)
    plain = result_bytes(Study(**kw).run())
    obs.enable(trace=True, metrics=True, journal=False)
    traced = result_bytes(Study(**kw).run())
    spans = [e for e in obs.tracer().export() if e.get("ph") == "X"]
    counters = dict(obs.metrics().counters)
    assert traced == plain

    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e)
    parents = {"scorer.code": "evaluate_batch",
               "scorer.program": "evaluate_batch",
               "scorer.run": "evaluate_batch",
               "scorer.area": "evaluate_batch",
               "evaluate_batch": "evaluator.call",
               "round.propose": "ask_tell_round",
               "round.dedup": "ask_tell_round",
               "evaluator.call": "ask_tell_round",
               "round.observe": "ask_tell_round",
               "search.materialize": "search_app",
               "search.export": "phase.search",
               "study.rebuild": "phase.search"}
    for child, parent in parents.items():
        assert by_name.get(child), f"no {child} span"
        for e in by_name[child]:
            assert _within(by_name[parent], e), f"{child} outside {parent}"
            if child != "evaluate_batch":      # the new spans
                assert "app" not in e["args"]
    # one bucket per app; the untraced study built both programs, so the
    # traced one reuses them
    assert counters["scorer.programs"] + counters["scorer.program_reuses"] \
        == 2
    assert counters["scorer.program_reuses"] == 2
    assert 0 < counters["scorer.rows"] <= counters["scorer.rows_padded"]


def test_frontend_trace_and_scorer_width_are_counted():
    """Tracing a zoo app records one `frontend.trace` span (its op count
    and unique op columns) and counts the instances of its grouped expert
    GEMMs (26 MoE layers x 3 GEMMs x top-6 routed rows in decode, x 64
    experts in prefill); a scorer call counts its padded rows x unique op
    columns.  Disabled, nothing is recorded, and the graphs are the same."""
    from repro.frontend.zoo import build_zoo_app
    from repro.kernels.costmodel import FusedJaxScorer
    apps = {"deepseek-v2-lite-16b:decode": 468,
            "deepseek-v2-lite-16b:prefill": 4992}
    build_zoo_app.cache_clear()
    plain = {a: build_zoo_app(a).op_stream().field_matrix for a in apps}
    assert len(obs.tracer()) == 0
    assert obs.metrics().export()["counters"] == {}

    build_zoo_app.cache_clear()
    obs.enable(trace=True, metrics=True, journal=False)
    streams = {}
    for app, instances in apps.items():
        before = obs.metrics().counters.get("frontend.expert_instances", 0)
        streams[app] = build_zoo_app(app).op_stream()
        assert obs.metrics().counters["frontend.expert_instances"] \
            - before == instances
        np.testing.assert_array_equal(streams[app].field_matrix,
                                      plain[app])
    spans = [e for e in obs.tracer().export()
             if e.get("name") == "frontend.trace"]
    assert [e["args"]["app"] for e in spans] == list(apps)
    for e in spans:
        stream = streams[e["args"]["app"]]
        assert e["args"]["ops"] == len(stream)
        assert e["args"]["columns"] == len(stream.dedup_columns()[0])

    space = default_space()
    batch = space.decode_batch(space.sample_indices(
        np.random.default_rng(0), 40))
    app = "deepseek-v2-lite-16b:decode"
    FusedJaxScorer(streams[app], space.hw,
                   domains=space.domains).metrics(batch.matrix)
    columns = len(streams[app].dedup_columns()[0])
    assert obs.metrics().counters["scorer.cells"] == 256 * columns


def test_spans_lie_on_the_profiler_host_plane(tmp_path):
    """With `jax` imported, an enabled span is also a profiler
    TraceAnnotation: the trace's host plane holds an event of the same
    name that starts where the span's epoch timestamp says."""
    jax = pytest.importorskip("jax")
    from jax.profiler import ProfileData
    obs.enable(trace=True, metrics=False, journal=False)
    try:
        jax.profiler.start_trace(str(tmp_path))
    except Exception as e:                  # noqa: BLE001
        pytest.skip(f"the profiler cannot start here: {e}")
    try:
        with obs.span("probe.outer", n=4):
            with obs.span("probe.inner"):
                jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    ours = {e["name"]: e for e in obs.tracer().export()
            if e.get("ph") == "X"}
    path = next(tmp_path.rglob("*.xplane.pb"))
    profile = ProfileData.from_file(str(path))
    start = None
    host = {}
    for plane in profile.planes:
        for key, value in plane.stats:
            if key == "profile_start_time":
                start = int(value)
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.setdefault(ev.name, ev.start_ns)
    assert start is not None
    for name in ("probe.outer", "probe.inner"):
        assert name in host, f"{name} not on the host plane"
        epoch_us = (start + host[name]) / 1e3
        assert abs(epoch_us - ours[name]["ts"]) <= 1000


# ------------------------------------------------------------------ journal

@pytest.mark.parametrize("workers", [1, 2])
def test_journal_one_record_per_round(workers, tmp_path):
    from repro.obs.journal import validate_record
    from repro.obs.validate import validate_journal

    obs.enable(trace=False, metrics=False, journal=True)
    result = run_study(workers=workers)
    records = obs.journal().records
    assert records, "journal must capture ask/tell rounds"
    for rec in records:
        validate_record(rec)
        assert rec["app"] in ("ptb", "wdl")
    # one record per scored pool: at least the engine's reported round
    # count per app (greedy scores its founding pool before round 1)
    for app in ("ptb", "wdl"):
        n = sum(1 for r in records if r["app"] == app)
        assert n >= result.per_app[app]["rounds"] >= 1

    path = tmp_path / "journal.jsonl"
    obs.journal().write_jsonl(path)
    on_disk = validate_journal(path, expect_min_records=len(records))
    keys = [(r["app"], r["engine"], r["seq"]) for r in on_disk]
    assert keys == sorted(keys), "JSONL must be in canonical order"


def test_journal_hypervolume_and_best_monotone():
    obs.enable(trace=False, metrics=False, journal=True)
    run_study(apps=["ptb"], engine="genetic",
              budget=SearchBudget(restarts=1, max_rounds=4,
                                  engine_kwargs={"population": 12}))
    recs = obs.journal().records
    hvs = [r["hypervolume"] for r in recs]
    bests = [r["best"] for r in recs if r["best"] is not None]
    assert all(hv is not None and hv >= 0 for hv in hvs)
    assert hvs == sorted(hvs), "front hypervolume can only grow"
    assert bests == sorted(bests), "incumbent best can only improve"


# -------------------------------------------------------------- attribution

def test_explain_matches_cost_model():
    """Evaluator.explain re-derives exactly the numbers the search
    scored: same total cycles/GOPS as evaluate_stream, shares summing to
    one, and a bottleneck label consistent with the per-op cycle max."""
    from repro.core.search import Evaluator

    spec = AppSpec.from_app("resnet")
    space = default_space()
    ev = Evaluator.for_space(spec.stream, space,
                             peak_weight_bits=spec.peak_weight_bits,
                             peak_input_bits=spec.peak_input_bits)
    cfg = space.sample(np.random.default_rng(0))
    exp = ev.explain(cfg)

    bd = evaluate_stream(cfg, spec.stream, space.hw,
                         spec.peak_weight_bits, spec.peak_input_bits)
    assert exp.total_cycles == float(bd.stream_cycles)
    assert len(exp.ops) == len(spec.stream)
    assert np.isclose(sum(op.latency_share for op in exp.ops), 1.0)
    for j, op in enumerate(exp.ops):
        assert op.total_cycles == float(bd.total_cycles[j])
        peak = {"compute": op.compute_cycles, "weight": op.weight_cycles,
                "input": op.input_cycles}[op.bottleneck]
        assert peak == op.total_cycles
        assert op.roofline in ("compute-bound", "memory-bound")
    if exp.valid:
        perf, _ = ev.score_with_area([cfg])
        if perf[0] > 0:
            assert np.isclose(exp.gops, perf[0])
    # the table renders without touching the numbers
    assert "GOPS" in exp.table(max_rows=5)
    assert json.loads(json.dumps(exp.to_json()))["gops"] == exp.gops


# ------------------------------------------------------- logging satellite

def test_degradation_warns_and_logs(tmp_path, caplog):
    """Serial degradation keeps its ParallelExecutionWarning (test/API
    compat) and now also emits a repro.* logger event."""
    from repro.dse import FaultPlan

    ex = ParallelExecutor(workers=2, max_retries=1,
                          fault=FaultPlan(state_dir=str(tmp_path / "f"),
                                          mode="raise", times=999))
    with caplog.at_level(logging.INFO, logger="repro"):
        with pytest.warns(ParallelExecutionWarning, match="serial"):
            run_study(executor=ex)
    assert ex.degraded
    events = [r for r in caplog.records
              if r.name.startswith("repro.")]
    assert any("pool.serial_degradation" in r.getMessage()
               for r in events)
    assert any("pool.retry" in r.getMessage() for r in events)


def test_repro_logger_is_quiet_by_default():
    logger = obs.get_logger("dse.parallel")
    assert logger.name == "repro.dse.parallel"
    root = logging.getLogger("repro")
    assert any(isinstance(h, logging.NullHandler) for h in root.handlers)
    with warnings.catch_warnings():
        warnings.simplefilter("error")      # no stray warnings from logging
        obs.log_event(logger, "debug", "noop", x=1)


# ---------------------------------------------------------------- validators

def test_validate_chrome_trace_rejects_malformed(tmp_path):
    from repro.obs.validate import validate_chrome_trace

    p = tmp_path / "t.json"
    p.write_text(json.dumps({"traceEvents": [
        {"name": "a", "ph": "X", "pid": 1, "tid": 1, "ts": 0}]}))
    with pytest.raises(ValueError, match="dur"):
        validate_chrome_trace(p)
    p.write_text(json.dumps([1, 2, 3]))
    with pytest.raises(ValueError, match="not a Chrome trace"):
        validate_chrome_trace(p)
    p.write_text(json.dumps({"traceEvents": [
        {"name": "a", "ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": 5}]}))
    with pytest.raises(ValueError, match="process"):
        validate_chrome_trace(p, expect_processes=2)


def test_validate_journal_rejects_malformed(tmp_path):
    from repro.obs.validate import validate_journal

    p = tmp_path / "j.jsonl"
    good = {"seq": 0, "kind": "round", "engine": "tpe", "round": 0,
            "pool": 8, "n_scored": 8, "best": 1.0, "feasible_frac": 1.0,
            "hypervolume": None}
    p.write_text(json.dumps(good) + "\n")
    assert validate_journal(p) == [good]
    bad = dict(good, kind="sandwich")
    p.write_text(json.dumps(bad) + "\n")
    with pytest.raises(ValueError, match="kind"):
        validate_journal(p)
    p.write_text("not json\n")
    with pytest.raises(ValueError, match="not JSON"):
        validate_journal(p)


def test_validate_cli_gates(tmp_path):
    from repro.obs.validate import main

    obs.enable(trace=True, metrics=False, journal=True)
    with obs.span("study"):
        obs.journal_record(kind="round", engine="tpe", round=0, pool=8,
                           n_scored=8, best=1.0, feasible_frac=1.0,
                           hypervolume=None)
    trace = tmp_path / "t.json"
    journal = tmp_path / "j.jsonl"
    obs.tracer().write(trace)
    obs.journal().write_jsonl(journal)
    assert main(["--trace", str(trace), "--journal", str(journal)]) == 0
    assert main(["--trace", str(trace), "--expect-processes", "5"]) == 2


# ------------------------------------------------------------- bench_io

def test_bench_io_envelope_roundtrip(tmp_path):
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]
                           / "benchmarks"))
    import bench_io

    payload = {"throughput": 123.0, "nested": {"a": [1, 2]}}
    p = bench_io.write_results(tmp_path / "BENCH_x.json", "x_bench",
                               payload)
    env = bench_io.read_envelope(p)
    assert env["bench_schema"] == bench_io.BENCH_SCHEMA
    assert env["bench"] == "x_bench"
    assert env["host"]["cpu_count"] == __import__("os").cpu_count()
    assert env["timestamp"].endswith("Z")
    assert bench_io.read_results(p) == payload

    # legacy flat baselines (pre-envelope) still read
    legacy = tmp_path / "BENCH_legacy.json"
    legacy.write_text(json.dumps(payload))
    assert bench_io.read_results(legacy) == payload
    env = bench_io.read_envelope(legacy)
    assert env["bench_schema"] == 1
    assert env["bench"] == "BENCH_legacy"
    assert env["host"] is None
