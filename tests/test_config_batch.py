"""Array-native evaluation pipeline: exact equivalence to the dataclass path.

The contract under test: `ConfigBatch` scoring (the fused numpy scorer
behind `performance_gops`), `area_many`, `repair_for_peaks_many`, and the
Evaluator's vectorized cache keys are *bit-identical* to the
per-dataclass `evaluate_stream_many` reference over randomized spaces,
streams (hand-built §5.1 graphs and traced zoo apps), peaks, and batch
compositions.  The device scorer is held to 1e-6 relative on GOPS.
"""

import dataclasses

import numpy as np
import pytest

from repro.core import apps
from repro.core.costmodel import (AccelConfig, ConfigBatch, FusedStreamScorer,
                                  HardwareConstants, Op, OpStream, area_many,
                                  evaluate_stream_many, performance_gops)
from repro.core.multiapp import AppSpec
from repro.core.search import (AnnealOptimizer, Evaluator, FunctionEvaluator,
                               GeneticOptimizer, GreedyOptimizer,
                               RandomSearchOptimizer, run_search)
from repro.core.space import DesignSpace, default_space

HW = HardwareConstants()


@pytest.fixture(scope="module")
def space():
    return default_space()


@pytest.fixture(scope="module")
def resnet_spec():
    return AppSpec.from_graph("resnet", apps.build_app("resnet"))


@pytest.fixture(scope="module")
def zoo_spec():
    return AppSpec.from_graph("qwen2-0.5b:decode",
                              apps.build_app("qwen2-0.5b:decode"))


def random_stream(rng: np.random.Generator) -> OpStream:
    ops = []
    for _ in range(int(rng.integers(1, 12))):
        kind = int(rng.integers(4))
        if kind == 0:
            nkx = int(rng.choice([1, 3, 5, 7]))
            ops.append(Op.conv2d(int(rng.integers(1, 128)),
                                 int(rng.integers(nkx, 64)),
                                 int(rng.integers(nkx, 64)), nkx, nkx,
                                 int(rng.integers(1, 256)),
                                 s=int(rng.choice([1, 2])),
                                 batch=int(rng.choice([1, 4, 128]))))
        elif kind == 1:
            ops.append(Op.depthwise(int(rng.integers(1, 64)), 28, 28, 3, 3))
        elif kind == 2:
            ops.append(Op.matvec(int(rng.integers(1, 4096)),
                                 int(rng.integers(1, 4096)),
                                 batch=int(rng.choice([1, 8]))))
        else:
            ops.append(Op.batched_matmul(int(rng.integers(1, 512)),
                                         int(rng.integers(1, 512)),
                                         int(rng.integers(1, 512)),
                                         instances=int(rng.integers(1, 32))))
    # duplicate a block so the column-dedup path is exercised
    return OpStream(ops + ops[: max(1, len(ops) // 2)])


def random_space(rng: np.random.Generator) -> DesignSpace:
    base = default_space()
    domains = {}
    for k, dom in base.domains.items():
        size = int(rng.integers(1, len(dom) + 1))
        vals = sorted(int(v) for v in
                      rng.choice(dom, size=size, replace=False))
        domains[k] = tuple(vals)
    return DesignSpace(domains=domains, hw=base.hw,
                       area_budget=float(rng.choice(
                           [0.0, base.area_budget, 30000.0])))


def assert_eval_equal(a, b, context=""):
    np.testing.assert_array_equal(a[0], b[0], err_msg=f"cycles {context}")
    np.testing.assert_array_equal(a[1], b[1], err_msg=f"valid {context}")
    for k in a[2]:
        np.testing.assert_array_equal(a[2][k], b[2][k],
                                      err_msg=f"parts[{k}] {context}")


def assert_fused_equal(scorer, batch, ref_gops, context=""):
    perf, area = scorer.metrics(batch.matrix)
    np.testing.assert_array_equal(perf, ref_gops,
                                  err_msg=f"fused gops {context}")
    np.testing.assert_array_equal(area, area_many(batch, scorer.hw),
                                  err_msg=f"fused area {context}")


def assert_gops_match_reference(batch, spec, space, context):
    """`performance_gops` (the fused numpy scorer) and
    `FusedStreamScorer.metrics` are bit-identical to the reference."""
    kw = dict(peak_weight_bits=spec.peak_weight_bits,
              peak_input_bits=spec.peak_input_bits)
    ref = evaluate_stream_many(batch.to_configs(), spec.stream, space.hw,
                               **kw)
    assert_eval_equal(evaluate_stream_many(batch, spec.stream, space.hw,
                                           **kw), ref, context)
    ref_gops = performance_gops(batch, spec.stream, space.hw,
                                backend="numpy-ref", **kw)
    np.testing.assert_array_equal(
        performance_gops(batch, spec.stream, space.hw, **kw), ref_gops,
        err_msg=context)
    assert_fused_equal(FusedStreamScorer(spec.stream, space.hw,
                                         domains=space.domains, **kw),
                       batch, ref_gops, context)


# -------------------------------------------------------------- ConfigBatch

def test_configbatch_roundtrip(space):
    rng = np.random.default_rng(0)
    cfgs = [space.sample(rng) for _ in range(17)]
    batch = ConfigBatch.from_configs(cfgs)
    assert len(batch) == 17
    assert batch.to_configs() == cfgs
    assert batch[3] == cfgs[3]
    assert list(batch)[5] == cfgs[5]
    sub = batch.take(np.asarray([2, 2, 9]))
    assert sub.to_configs() == [cfgs[2], cfgs[2], cfgs[9]]
    both = ConfigBatch.concat([batch, sub])
    assert len(both) == 20
    # row keys: equal configs <=> equal keys
    keys = batch.row_keys()
    assert keys[2] == sub.row_keys()[0]
    assert len(set(keys)) == len({tuple(sorted(c.asdict().items()))
                                  for c in cfgs})
    # identity on an existing batch
    assert ConfigBatch.from_configs(batch) is batch


def test_configbatch_from_columns_defaults():
    b = ConfigBatch.from_columns(pe_group=np.asarray([2, 4]),
                                 tif=np.asarray([8, 16]))
    assert b[0] == AccelConfig(pe_group=2, tif=8)
    assert b[1] == AccelConfig(pe_group=4, tif=16)
    with pytest.raises(ValueError):
        ConfigBatch.from_columns(nonsense=np.asarray([1]))


def test_decode_batch_matches_decode_over_random_spaces():
    rng = np.random.default_rng(1)
    for _ in range(10):
        sp = random_space(rng)
        idx = sp.sample_indices(rng, int(rng.integers(1, 60)))
        batch = sp.decode_batch(idx)
        via_dataclasses = ConfigBatch.from_configs(sp.decode(idx))
        np.testing.assert_array_equal(batch.matrix, via_dataclasses.matrix)
        np.testing.assert_array_equal(sp.encode_batch(batch), idx)


# ------------------------------------------------------------ scoring parity

def test_area_many_bit_identical(space):
    rng = np.random.default_rng(2)
    cfgs = [space.sample(rng) for _ in range(64)]
    np.testing.assert_array_equal(area_many(cfgs, HW),
                                  np.asarray([c.area(HW) for c in cfgs]))
    np.testing.assert_array_equal(
        area_many(ConfigBatch.from_configs(cfgs), HW),
        np.asarray([c.area(HW) for c in cfgs]))


def test_scoring_parity_randomized():
    """list-of-dataclass vs ConfigBatch vs the fused scorer against the
    reference, randomized streams/pools/peaks: bit-identical cycles,
    validity, parts, GOPS and area."""
    rng = np.random.default_rng(3)
    for trial in range(8):
        sp = random_space(rng)
        stream = random_stream(rng)
        # pool sizes from one row to several fused-scorer chunks' worth
        n = int(rng.choice([1, 7, 63, 64, 65, 200]))
        idx = sp.sample_indices(rng, n)
        cfgs = sp.decode(idx)
        batch = sp.decode_batch(idx)
        pw = int(rng.integers(0, 2)) * int(rng.integers(0, 1 << 24))
        pi = int(rng.integers(0, 2)) * int(rng.integers(0, 1 << 24))
        ref = evaluate_stream_many(cfgs, stream, HW, pw, pi)
        ctx = f"trial={trial} n={n}"
        assert_eval_equal(
            evaluate_stream_many(batch, stream, HW, pw, pi), ref, ctx)
        ref_gops = performance_gops(cfgs, stream, HW, pw, pi,
                                    backend="numpy-ref")
        np.testing.assert_array_equal(
            performance_gops(batch, stream, HW, pw, pi), ref_gops,
            err_msg=ctx)
        np.testing.assert_array_equal(
            performance_gops(cfgs, stream, HW, pw, pi), ref_gops,
            err_msg=ctx)
        assert_fused_equal(FusedStreamScorer(stream, HW, pw, pi,
                                             domains=sp.domains),
                           batch, ref_gops, ctx)


@pytest.mark.parametrize("app", ["resnet", "ptb", "wdl", "fasterRCNN"])
def test_scoring_parity_handbuilt_apps(space, app):
    spec = AppSpec.from_graph(app, apps.build_app(app))
    rng = np.random.default_rng(4)
    batch = space.decode_batch(space.sample_indices(rng, 128))
    assert_gops_match_reference(batch, spec, space, app)


def test_scoring_parity_traced_zoo_app(space, zoo_spec):
    rng = np.random.default_rng(5)
    batch = space.decode_batch(space.sample_indices(rng, 128))
    assert_gops_match_reference(batch, zoo_spec, space, "zoo")


def test_jax_backend_matches_numpy(space, resnet_spec, zoo_spec):
    """The device scorer's GOPS within 1e-6 relative of the reference
    (exact in practice on the CPU: the program runs the same int64/float64
    formulas under x64)."""
    pytest.importorskip("jax")
    from repro.kernels.costmodel import FusedJaxScorer
    rng = np.random.default_rng(6)
    for spec in (resnet_spec, zoo_spec):
        batch = space.decode_batch(space.sample_indices(rng, 96))
        kw = dict(peak_weight_bits=spec.peak_weight_bits,
                  peak_input_bits=spec.peak_input_bits)
        ref = performance_gops(batch, spec.stream, space.hw,
                               backend="numpy-ref", **kw)
        jx, _ = FusedJaxScorer(spec.stream, space.hw, domains=space.domains,
                               **kw).metrics(batch.matrix)
        rel = np.abs(jx - ref) / np.maximum(np.abs(ref), 1e-30)
        assert float(rel.max()) <= 1e-6


@pytest.mark.parametrize("backend", ["jax", "numpy-fast"])
def test_performance_gops_rejects_unknown_backend(space, resnet_spec,
                                                  backend):
    batch = space.decode_batch(space.sample_indices(
        np.random.default_rng(8), 4))
    with pytest.raises(ValueError, match="unknown backend"):
        performance_gops(batch, resnet_spec.stream, space.hw,
                         backend=backend)


# -------------------------------------------------------------- repair parity

def _repair_cases():
    """(space, [N, V] index pool, peak_weight_bits, peak_input_bits) per
    parity case, drawn from a fixed seed."""
    rng = np.random.default_rng(7)
    cases = {"random-small": []}
    for _ in range(12):
        sp = random_space(rng)
        idx = sp.sample_indices(rng, int(rng.integers(1, 48)))
        pw = int(rng.integers(0, 3)) * int(rng.integers(0, 1 << 26))
        pi = int(rng.integers(0, 3)) * int(rng.integers(0, 1 << 26))
        cases["random-small"].append((sp, idx, pw, pi))
    # the benchmark cells' real floors: qwen2.5-32b prefill and decode
    sp = default_space()
    for name, pw, pi in (("qwen-prefill", 0, 183541760),
                         ("qwen-decode", 0, 3318280),
                         ("weight-floor", 1 << 26, 3318280)):
        cases[name] = [(sp, sp.sample_indices(rng, 4096), pw, pi)]
    for budget in (0.0, default_space().area_budget, 30000.0):
        cases[f"random-large-{budget:g}"] = []
        for _ in range(3):
            sp = dataclasses.replace(random_space(rng), area_budget=budget)
            idx = sp.sample_indices(rng, int(rng.integers(1, 2049)))
            pw = int(rng.integers(0, 3)) * int(rng.integers(0, 1 << 28))
            pi = int(rng.integers(0, 3)) * int(rng.integers(0, 1 << 30))
            cases[f"random-large-{budget:g}"].append((sp, idx, pw, pi))
    # more than 64 bank heights: the scalar loop's 64-bump cap binds
    base = default_space()
    sp = DesignSpace(domains={**base.domains,
                              "bank_height": tuple(range(16, 16 * 100, 16))},
                     hw=base.hw, area_budget=base.area_budget)
    cases["bump-cap"] = [(sp, sp.sample_indices(rng, 512), 1 << 30, 1 << 30),
                         (sp, sp.sample_indices(rng, 512), 1 << 20, 1 << 25)]
    return cases


REPAIR_CASES = _repair_cases()


@pytest.mark.parametrize("case", sorted(REPAIR_CASES))
def test_repair_many_bit_identical_over_random_spaces(case):
    for trial, (sp, idx, pw, pi) in enumerate(REPAIR_CASES[case]):
        scalar = [sp.repair_for_peaks(c, pw, pi) for c in sp.decode(idx)]
        batched = sp.repair_for_peaks_many(sp.decode_batch(idx), pw, pi)
        np.testing.assert_array_equal(
            batched.matrix, ConfigBatch.from_configs(scalar).matrix,
            err_msg=f"case={case} trial={trial} pw={pw} pi={pi}")


def test_repair_many_cap_binds_on_a_long_domain():
    """The bump-cap case really exercises the cap: the scalar repair stops
    after 64 bank-height steps, short of the floor."""
    sp, idx, pw, pi = REPAIR_CASES["bump-cap"][0]
    cfg = sp.decode(idx[:1])[0]
    cfg = dataclasses.replace(cfg, bank_height=16, weight_banks_pg=1,
                              bank_width=16, pe_group=1)
    got = sp.repair_for_peaks(cfg, pw, 0)
    assert got.bank_height == 16 * 65
    assert got.weight_buffer_bits() < pw
    assert sp.repair_for_peaks_many([cfg], pw, 0).to_configs() == [got]


@pytest.mark.parametrize("as_batch", [False, True])
def test_repair_many_accepts_config_sequence(space, as_batch):
    rng = np.random.default_rng(8)
    cfgs = [space.sample(rng) for _ in range(9)]
    given = ConfigBatch.from_configs(cfgs) if as_batch else list(cfgs)
    before = ConfigBatch.from_configs(given).matrix.copy()
    got = space.repair_for_peaks_many(given, 1 << 22, 1 << 22)
    want = [space.repair_for_peaks(c, 1 << 22, 1 << 22) for c in cfgs]
    assert got.to_configs() == want
    assert got.to_configs() != cfgs          # the repair did move rows
    # inputs are untouched (repair copies)
    np.testing.assert_array_equal(ConfigBatch.from_configs(given).matrix,
                                  before)
    assert ConfigBatch.from_configs(given).to_configs() == cfgs


# ------------------------------------------------------- evaluator + engines

def test_evaluator_batch_composition_invariance(space, resnet_spec):
    """Scores are identical whether a pool arrives as a dataclass list, a
    ConfigBatch, split into slices, or re-ordered duplicates — the cache
    must be invisible in every composition."""
    rng = np.random.default_rng(9)
    idx = space.sample_indices(rng, 40)
    batch = space.decode_batch(idx)
    cfgs = batch.to_configs()
    kw = dict(peak_weight_bits=resnet_spec.peak_weight_bits,
              peak_input_bits=resnet_spec.peak_input_bits)

    direct = performance_gops(batch, resnet_spec.stream, space.hw, **kw)
    areas = area_many(batch, space.hw)
    direct = np.where(areas <= space.area_budget, direct, 0.0)

    ev = Evaluator.for_space(resnet_spec.stream, space, **kw)
    np.testing.assert_array_equal(ev(batch), direct)

    ev2 = Evaluator.for_space(resnet_spec.stream, space, **kw)
    np.testing.assert_array_equal(ev2(cfgs), direct)

    ev3 = Evaluator.for_space(resnet_spec.stream, space, **kw)
    np.testing.assert_array_equal(
        np.concatenate([ev3(batch[:13]), ev3(batch[13:])]), direct)

    # duplicates inside a batch pool hit the vectorized key path once
    dup = ConfigBatch.concat([batch, batch.take(np.arange(5))])
    ev4 = Evaluator.for_space(resnet_spec.stream, space, **kw)
    got = ev4(dup)
    np.testing.assert_array_equal(got[:40], direct)
    np.testing.assert_array_equal(got[40:], direct[:5])
    assert ev4.n_scored == 40

    # warm cache returns identical values, zero new model calls
    scored_before = ev.n_scored
    np.testing.assert_array_equal(ev(cfgs), direct)
    assert ev.n_scored == scored_before


def test_engines_propose_array_native_pools(space, resnet_spec):
    """On the accelerator DesignSpace the population engines keep pools as
    ConfigBatch end to end, and results still materialize to dataclasses."""
    kw = dict(peak_weight_bits=resnet_spec.peak_weight_bits,
              peak_input_bits=resnet_spec.peak_input_bits)
    for engine_cls, ctor_kw in (
            (RandomSearchOptimizer, dict(max_rounds=2, batch=8)),
            (AnnealOptimizer, dict(max_rounds=3, chains=4)),
            (GeneticOptimizer, dict(max_rounds=2, population=8)),
            (GreedyOptimizer, dict(max_rounds=2, k=1)),
    ):
        ev = Evaluator.for_space(resnet_spec.stream, space, **kw)
        eng = engine_cls(space, ev, seed=0, **ctor_kw)
        saw_batch = False
        while not eng.done:
            pool = eng.propose()
            if len(pool) == 0:
                break
            saw_batch = saw_batch or isinstance(pool, ConfigBatch)
            eng.observe(pool, ev(pool))
        assert saw_batch, engine_cls.name
        assert isinstance(eng.best, AccelConfig)

    ev = Evaluator.for_space(resnet_spec.stream, space, **kw)
    res = run_search(RandomSearchOptimizer(space, ev, seed=1, max_rounds=2,
                                           batch=6), ev)
    assert len(res.evaluated) == 12
    assert all(isinstance(c, AccelConfig) for c in res.evaluated)


def _exec_point_score(pt) -> float:
    return float(pt.microbatches) / (1 + abs(pt.attn_kv_block - 2048))


@pytest.mark.parametrize("engine,kw", [
    ("random", dict(max_rounds=2, batch=6)),
    ("greedy", dict(max_rounds=3, k=1)),           # round 0 is a list
    ("anneal", dict(max_rounds=3, chains=4)),
    ("genetic", dict(max_rounds=2, population=8)),
    ("exec_space", dict(max_rounds=2, batch=4)),   # generic ExecPoints
])
def test_run_search_log_type(engine, kw, space, resnet_spec):
    """Over the accelerator space the evaluated log is one `ConfigBatch`
    whose rows are the per-config log of the rounds' pools, and whose
    items are still `AccelConfig`; a generic space keeps a list.  Counter
    `search.rows_materialized` reads 0 for a batch log and the rows
    logged for a list."""
    from repro import obs
    from repro.core.search import make_engine
    if engine == "exec_space":
        from repro.core.autotune import exec_space
        sp, ev = exec_space("train"), FunctionEvaluator(_exec_point_score)
        eng = make_engine("random", sp, ev, seed=0, **kw)
    else:
        ev = Evaluator.for_space(
            resnet_spec.stream, space,
            peak_weight_bits=resnet_spec.peak_weight_bits,
            peak_input_bits=resnet_spec.peak_input_bits)
        eng = make_engine(engine, space, ev, seed=0, **kw)
    pools = []
    propose = eng.propose

    def recording_propose():
        pool = propose()
        pools.append(pool)
        return pool

    eng.propose = recording_propose
    obs.disable(reset=True)
    obs.enable(trace=False, metrics=True, journal=False)
    try:
        res = run_search(eng, ev)
        rows_materialized = obs.metrics().counters["search.rows_materialized"]
    finally:
        obs.disable(reset=True)
    old_log = [b[i] for b in pools for i in range(len(b))]
    assert len(res.evaluated) == len(old_log) > 0
    if engine == "exec_space":
        assert isinstance(res.evaluated, list)
        assert res.evaluated == old_log
        assert rows_materialized == len(old_log)
        return
    assert isinstance(res.evaluated, ConfigBatch)
    np.testing.assert_array_equal(res.evaluated.matrix,
                                  ConfigBatch.from_configs(old_log).matrix)
    assert all(isinstance(c, AccelConfig) for c in res.evaluated)
    assert rows_materialized == 0


@pytest.mark.parametrize("kinds,want", [
    (("batch", "batch", "batch"), ConfigBatch),
    (("batch", "empty", "batch"), ConfigBatch),
    (("list", "batch"), ConfigBatch),              # AccelConfig lists join
    (("batch", "generic"), list),
    (("generic", "generic"), list),
])
def test_search_result_merge_log_type(kinds, want, space):
    """`SearchResult.merge` joins batch logs into one batch in the given
    order and falls back to a list on mixed or generic logs; either way
    the merged log reads back the parts' configs in order."""
    from repro.core.search import SearchResult
    rng = np.random.default_rng(3)
    logs, flat = [], []
    for kind in kinds:
        cfgs = [space.sample(rng) for _ in range(3)]
        if kind == "batch":
            logs.append(ConfigBatch.from_configs(cfgs))
        elif kind == "list":
            logs.append(cfgs)
        elif kind == "generic":
            cfgs = [{"tile": int(c.tif)} for c in cfgs]
            logs.append(cfgs)
        else:
            cfgs = []
            logs.append([])
        flat.extend(cfgs)
    results = [SearchResult(best=None, best_perf=float(i), history=[],
                            evaluated=log,
                            evaluated_perf=np.arange(len(log), dtype=float),
                            rounds=1)
               for i, log in enumerate(logs)]
    merged = SearchResult.merge(results)
    assert isinstance(merged.evaluated, want)
    assert list(merged.evaluated) == flat
    assert len(merged.evaluated_perf) == len(flat)


def test_function_evaluator_batch_score_fn():
    calls = {"scalar": 0, "batch": 0}

    def scalar_fn(cfg):
        calls["scalar"] += 1
        return float(cfg.tif + cfg.pe_group)

    def batch_fn(cfgs):
        calls["batch"] += 1
        return [float(c.tif + c.pe_group) for c in cfgs]

    rng = np.random.default_rng(10)
    sp = default_space()
    pool = [sp.sample(rng) for _ in range(11)]
    pool = pool + pool[:4]                        # in-pool duplicates

    plain = FunctionEvaluator(scalar_fn)
    want = plain(pool)

    batched = FunctionEvaluator(scalar_fn, batch_score_fn=batch_fn)
    got = batched(pool)
    np.testing.assert_array_equal(got, want)
    assert calls["batch"] == 1                    # ONE call for the miss set
    assert batched.n_scored == 11                 # unique misses only
    # second call: pure cache, no new batch calls
    np.testing.assert_array_equal(batched(pool), want)
    assert calls["batch"] == 1

    def bad_batch(cfgs):
        return [0.0]

    broken = FunctionEvaluator(scalar_fn, batch_score_fn=bad_batch)
    with pytest.raises(ValueError):
        broken(pool)


def test_stream_column_dedup_roundtrip(resnet_spec):
    stream = resnet_spec.stream
    view, expand = stream.dedup_columns()
    assert len(view) <= len(stream)
    np.testing.assert_array_equal(view.field_matrix[:, expand],
                                  stream.field_matrix)
    # cached: second call returns the same objects
    assert stream.dedup_columns()[0] is view
