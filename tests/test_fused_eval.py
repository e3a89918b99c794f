"""Fused evaluation hot path: exactness under hashing, caching, and dedup.

The contract under test: the fused single-pass scorer
(`FusedStreamScorer`) and the hashed row cache (`RowHashCache`) behind the
live `Evaluator` are *bit-identical* to the reference pipeline
(`performance_gops(backend="numpy-ref")` + `area_many` + a
tobytes()-keyed cache) over randomized spaces, pools, batch
compositions, in-pool duplicates — and under adversarial hashing (every
row forced onto one hash bucket).  The jax fused scorer is held to 1e-6
relative.  Cross-round dedup is pure bookkeeping: counts land in the
journal, scores never change.
"""

import numpy as np
import pytest

from repro import obs
from repro.core import apps
from repro.core.costmodel import (ConfigBatch, FusedStreamScorer, area_many,
                                  performance_gops)
from repro.core.multiapp import AppSpec
from repro.core.search import (Evaluator, RandomSearchOptimizer, run_search,
                               RowHashCache, first_occurrence, hash_rows)
from repro.core.search import rowcache
from repro.core.space import DesignSpace, default_space


@pytest.fixture(scope="module")
def space():
    return default_space()


@pytest.fixture(scope="module")
def resnet_spec():
    return AppSpec.from_graph("resnet", apps.build_app("resnet"))


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


def make_evaluators(spec, space):
    kw = dict(peak_weight_bits=spec.peak_weight_bits,
              peak_input_bits=spec.peak_input_bits)
    fused = Evaluator.for_space(spec.stream, space, **kw)
    ref = Evaluator.for_space(spec.stream, space, backend="numpy-ref", **kw)
    return fused, ref


# ----------------------------------------------------------------- hashing

def test_hash_rows_deterministic_and_sensitive():
    rng = np.random.default_rng(0)
    m = rng.integers(0, 64, size=(500, 18)).astype(np.int64)
    h = hash_rows(m)
    assert h.dtype == np.uint64 and h.shape == (500,)
    np.testing.assert_array_equal(h, hash_rows(m.copy()))
    # single-element change flips the hash (w.h.p.; deterministic here)
    m2 = m.copy()
    m2[7, 3] += 1
    assert hash_rows(m2)[7] != h[7]
    # column position matters: swapping two unequal columns changes rows
    m3 = m[:, ::-1].copy()
    assert (hash_rows(m3) != h).any()
    # no collisions across 50k distinct rows (seeded, so stable)
    big = np.arange(50_000, dtype=np.int64).reshape(-1, 1) * np.ones(
        (1, 4), dtype=np.int64)
    assert len(np.unique(hash_rows(big))) == 50_000


def test_first_occurrence_matches_dict_reference():
    rng = np.random.default_rng(1)
    for trial in range(20):
        n = int(rng.integers(1, 400))
        # tiny value range forces heavy duplication
        m = rng.integers(0, 3, size=(n, 5)).astype(np.int64)
        ref, seen = [], {}
        for i, row in enumerate(m):
            k = row.tobytes()
            ref.append(seen.setdefault(k, i))
        ref = np.asarray(ref)
        np.testing.assert_array_equal(first_occurrence(m, hash_rows(m)), ref)
        # adversarial: every row on one hash bucket -> pure bytes fallback
        np.testing.assert_array_equal(
            first_occurrence(m, np.zeros(n, dtype=np.uint64)), ref)


# ------------------------------------------------------------ RowHashCache

def test_rowhashcache_roundtrip_and_misses():
    rng = np.random.default_rng(2)
    m = rng.integers(-1000, 1000, size=(300, 6)).astype(np.int64)
    m = m[first_occurrence(m, hash_rows(m)) == np.arange(len(m))]
    h = hash_rows(m)
    vals = rng.random((len(m), 2))
    c = RowHashCache(6, 1 << 12)
    found0, _ = c.lookup(m, h)
    assert not found0.any()
    c.insert(m, h, vals)
    found, got = c.lookup(m, h)
    assert found.all()
    np.testing.assert_array_equal(got, vals)
    # absent rows stay misses
    other = m + 5000
    found2, _ = c.lookup(other, hash_rows(other))
    assert not found2.any()
    assert len(c) == len(m)


def test_rowhashcache_forced_collisions_stay_exact():
    rng = np.random.default_rng(3)
    m = np.unique(rng.integers(0, 100, size=(64, 4)).astype(np.int64),
                  axis=0)
    vals = np.arange(len(m) * 2, dtype=np.float64).reshape(-1, 2)
    # every row claims the SAME hash: correctness must come from the
    # exact-key fallback, not the hash
    h = np.full(len(m), 7, dtype=np.uint64)
    c = RowHashCache(4, 1 << 12)
    c.insert(m, h, vals)
    found, got = c.lookup(m, h)
    assert found.all()
    np.testing.assert_array_equal(got, vals)
    # a different row with the same hash is still a miss
    probe = m[:1] + 999
    found2, _ = c.lookup(probe, np.full(1, 7, dtype=np.uint64))
    assert not found2.any()


def test_rowhashcache_eviction_bound_keeps_newest():
    rng = np.random.default_rng(4)
    c = RowHashCache(3, maxsize=64)
    total = 0
    for _ in range(10):
        m = rng.integers(0, 10**6, size=(40, 3)).astype(np.int64)
        m = m[first_occurrence(m, hash_rows(m)) == np.arange(len(m))]
        h = hash_rows(m)
        c.insert(m, h, np.zeros((len(m), 2)))
        total += len(m)
        assert len(c) <= 64
        # the batch just inserted survives its own insert's eviction pass
        found, _ = c.lookup(m, h)
        assert found.all()
    assert c.evictions > 0
    assert c.evictions >= total - 64


def test_rowhashcache_export_merge_wire_format():
    rng = np.random.default_rng(5)
    m = rng.integers(0, 50, size=(30, 4)).astype(np.int64)
    m = m[first_occurrence(m, hash_rows(m)) == np.arange(len(m))]
    vals = rng.random((len(m), 2))
    c = RowHashCache(4, 1 << 10)
    c.insert(m, hash_rows(m), vals)
    exported = c.export_bytes()
    # wire format: row tobytes() -> (v0, v1), same keys the old
    # tobytes()-keyed LRU used
    assert set(exported) == {row.tobytes() for row in m}
    d = RowHashCache(4, 1 << 10)
    assert d.merge_bytes(exported) == len(m)
    # merge is counter-neutral
    assert d.hits == 0 and d.misses == 0
    found, got = d.lookup(m, hash_rows(m))
    assert found.all()
    np.testing.assert_array_equal(got, vals)
    # re-merge is a no-op
    assert d.merge_bytes(exported) == 0


# ---------------------------------------------------- Evaluator bit-identity

def test_evaluator_bit_identical_random_spaces(resnet_spec):
    rng = np.random.default_rng(6)
    for trial in range(6):
        sp = random_space(rng)
        fused, ref = make_evaluators(resnet_spec, sp)
        n = int(rng.integers(1, 300))
        batch = sp.decode_batch(sp.sample_indices(rng, n))
        # random batch composition: score in uneven chunks, with a
        # duplicated chunk so cross-call cache hits are exercised
        cuts = np.sort(rng.integers(0, n + 1, size=2))
        parts = [batch.take(np.arange(0, cuts[0])),
                 batch.take(np.arange(cuts[0], cuts[1])),
                 batch.take(np.arange(cuts[1], n)),
                 batch.take(np.arange(0, cuts[0]))]
        for part in parts:
            if len(part) == 0:
                continue
            pf, af = fused.score_with_area(part)
            pr, ar = ref.score_with_area(part)
            np.testing.assert_array_equal(pf, pr)
            np.testing.assert_array_equal(af, ar)
        assert fused.cache_hits == ref.cache_hits
        assert fused.cache_misses == ref.cache_misses


def test_evaluator_in_pool_duplicates_and_counters(resnet_spec, space):
    rng = np.random.default_rng(7)
    fused, ref = make_evaluators(resnet_spec, space)
    batch = space.decode_batch(space.sample_indices(rng, 50))
    take = np.asarray([0, 1, 1, 2, 0, 3] + list(range(4, 50)))
    dup = batch.take(take)
    pf, af = fused.score_with_area(dup)
    pr, ar = ref.score_with_area(dup)
    np.testing.assert_array_equal(pf, pr)
    np.testing.assert_array_equal(af, ar)
    # in-pool duplicates are neither hits nor misses (legacy semantics)
    assert fused.cache_hits == ref.cache_hits == 0
    assert fused.cache_misses == ref.cache_misses == 50
    # full repeat: all hits
    fused.score_with_area(dup)
    assert fused.cache_hits == 50


def test_evaluator_exact_under_forced_hash_collisions(resnet_spec, space,
                                                      monkeypatch):
    # degenerate 4-bucket hash: the cache lives or dies by its exact-key
    # fallback; results must not move by a bit
    real = rowcache.hash_rows

    def low_entropy(matrix):
        return real(matrix) % np.uint64(4)

    rng = np.random.default_rng(8)
    batch = space.decode_batch(space.sample_indices(rng, 200))
    _, ref = make_evaluators(resnet_spec, space)
    want = ref.score_with_area(batch)
    monkeypatch.setattr(rowcache, "hash_rows", low_entropy)
    fused, _ = make_evaluators(resnet_spec, space)
    got = fused.score_with_area(batch)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    # repeat pass: every row served from cache despite collisions
    fused.score_with_area(batch)
    assert fused.cache_hits == len(batch)
    np.testing.assert_array_equal(fused.score_with_area(batch)[0], want[0])


def test_evaluator_cache_export_merge_bit_identical(resnet_spec, space):
    rng = np.random.default_rng(9)
    batch = space.decode_batch(space.sample_indices(rng, 100))
    a, _ = make_evaluators(resnet_spec, space)
    want = a.score_with_area(batch)
    b, _ = make_evaluators(resnet_spec, space)
    assert b.cache_merge(a.cache_export()) == 100
    got = b.score_with_area(batch)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    # merged rows are hits, not rescored; merge itself is counter-neutral
    assert b.cache_hits == 100 and b.cache_misses == 0
    assert b.n_scored == 0


def test_evaluator_stats_surface(resnet_spec, space):
    fused, _ = make_evaluators(resnet_spec, space)
    stats = fused.stats()
    for key in ("cache_hits", "cache_misses", "cache_size",
                "cache_evictions", "dedup_skipped", "scored", "batches"):
        assert key in stats, key


# --------------------------------------------------------- fused zoo parity

def test_fused_scorer_bit_identical_all_zoo_apps(space):
    rng = np.random.default_rng(10)
    kw_hw = space.hw
    for name in apps.zoo_app_names():
        spec = AppSpec.from_graph(name, apps.build_app(name))
        batch = space.decode_batch(space.sample_indices(rng, 64))
        pw, pi = spec.peak_weight_bits, spec.peak_input_bits
        scorer = FusedStreamScorer(spec.stream, kw_hw, pw, pi,
                                   domains=space.domains)
        perf, area = scorer.metrics(batch.matrix)
        ref = performance_gops(batch, spec.stream, kw_hw, pw, pi,
                               backend="numpy-ref")
        np.testing.assert_array_equal(perf, ref, err_msg=name)
        np.testing.assert_array_equal(area, area_many(batch, kw_hw),
                                      err_msg=name)


# ------------------------------------------------------- cross-round dedup

def test_cross_round_dedup_counts_only(resnet_spec, space):
    obs.enable(trace=False, metrics=False, journal=True)
    try:
        kw = dict(peak_weight_bits=resnet_spec.peak_weight_bits,
                  peak_input_bits=resnet_spec.peak_input_bits)
        ev = Evaluator.for_space(resnet_spec.stream, space, **kw)
        eng = RandomSearchOptimizer(space, ev, batch=32, max_rounds=4,
                                    seed=0)
        res = run_search(eng, ev)
        recs = [r for r in obs.journal().records if r["kind"] == "round"]
        assert recs and all("dedup_skipped" in r for r in recs)
        assert all(isinstance(r["dedup_skipped"], int)
                   and r["dedup_skipped"] >= 0 for r in recs)
        # the evaluator accumulator is exactly the journal sum
        assert ev.dedup_skipped == sum(r["dedup_skipped"] for r in recs)
        # dedup is bookkeeping only: same engine/seed without a journal
        # produces identical scores
        ev2 = Evaluator.for_space(resnet_spec.stream, space, **kw)
        eng2 = RandomSearchOptimizer(space, ev2, batch=32, max_rounds=4,
                                     seed=0)
        obs.disable()
        res2 = run_search(eng2, ev2)
        np.testing.assert_array_equal(res.evaluated_perf,
                                      res2.evaluated_perf)
    finally:
        obs.disable()


def test_cross_round_dedup_counts_repeats(resnet_spec, space):
    rng = np.random.default_rng(11)
    batch = space.decode_batch(space.sample_indices(rng, 16))

    class Repeater:
        """Proposes the same pool every round."""
        name = "repeater"

        def __init__(self):
            self.rounds = 0
            self.best = None
            self.best_perf = float("-inf")
            self.history = []
            self.observes_vector = False

        def propose(self):
            return batch

        def _scalar(self, s):
            return s

        def observe(self, pool, scores):
            self.rounds += 1

        @property
        def done(self):
            return self.rounds >= 3

    kw = dict(peak_weight_bits=resnet_spec.peak_weight_bits,
              peak_input_bits=resnet_spec.peak_input_bits)
    ev = Evaluator.for_space(resnet_spec.stream, space, **kw)
    run_search(Repeater(), ev)
    # round 1 is all-new; rounds 2 and 3 are entirely repeats
    assert ev.dedup_skipped == 2 * len(batch)
    assert ev.stats()["dedup_skipped"] == 2 * len(batch)


# ------------------------------------------------------------- jax parity

@pytest.fixture
def fresh_programs():
    """An empty process-wide program map, so that counts of programs
    built do not depend on which tests ran before in this process."""
    pytest.importorskip("jax")
    from repro.kernels import costmodel
    costmodel._PROGRAMS.clear()
    obs.enable(trace=True, metrics=True, journal=False)
    try:
        yield costmodel._PROGRAMS
    finally:
        obs.disable(reset=True)
        costmodel._PROGRAMS.clear()


def _program_spans():
    return [e["args"] for e in obs.tracer().export()
            if e.get("name") == "scorer.program"]


def test_fused_jax_scorer_parity(resnet_spec, space, fresh_programs):
    from repro.kernels.costmodel import FusedJaxScorer
    rng = np.random.default_rng(12)
    batch = space.decode_batch(space.sample_indices(rng, 300))
    pw, pi = resnet_spec.peak_weight_bits, resnet_spec.peak_input_bits
    ref = FusedStreamScorer(resnet_spec.stream, space.hw, pw, pi,
                            domains=space.domains)
    want_p, want_a = ref.metrics(batch.matrix)
    jx = FusedJaxScorer(resnet_spec.stream, space.hw, pw, pi,
                        domains=space.domains)
    got_p, got_a = jx.metrics(batch.matrix)
    rel = np.abs(got_p - want_p) / np.maximum(np.abs(want_p), 1e-30)
    assert float(rel.max()) <= 1e-6
    rel_a = np.abs(got_a - want_a) / np.maximum(np.abs(want_a), 1e-30)
    assert float(rel_a.max()) <= 1e-6
    # ragged pool sizes fall into the same padded bucket: no recompile
    n0 = obs.metrics().counters["scorer.programs"]
    for n in (300, 301, 299, 260):
        jx.metrics(batch.matrix[:n])
    assert obs.metrics().counters["scorer.programs"] == n0 == 1


def test_scorers_of_one_shape_share_one_program(resnet_spec, space,
                                                fresh_programs):
    """A second scorer on the same stream, domains and bucket (a later
    `Study`'s) reuses the first one's program: it only uploads."""
    from repro.kernels.costmodel import FusedJaxScorer
    rng = np.random.default_rng(13)
    batch = space.decode_batch(space.sample_indices(rng, 200))
    args = (resnet_spec.stream, space.hw, resnet_spec.peak_weight_bits,
            resnet_spec.peak_input_bits)
    first = FusedJaxScorer(*args, domains=space.domains).metrics(
        batch.matrix)
    second = FusedJaxScorer(*args, domains=space.domains).metrics(
        batch.matrix)
    counters = obs.metrics().counters
    assert counters["scorer.programs"] == 1
    assert counters["scorer.program_reuses"] == 1
    assert len(fresh_programs) == 1
    assert [(a["upload"], a["reused"]) for a in _program_spans()] == \
        [(True, False), (True, True)]
    for a, b in zip(first, second):
        assert a.tobytes() == b.tobytes()


def test_tables_of_other_shapes_get_their_own_program(space,
                                                      fresh_programs):
    from repro.kernels.costmodel import FusedJaxScorer
    rng = np.random.default_rng(14)
    batch = space.decode_batch(space.sample_indices(rng, 100))
    for app in ("resnet", "ptb"):
        spec = AppSpec.from_graph(app, apps.build_app(app))
        ref = FusedStreamScorer(spec.stream, space.hw,
                                spec.peak_weight_bits, spec.peak_input_bits,
                                domains=space.domains)
        jx = FusedJaxScorer(spec.stream, space.hw, spec.peak_weight_bits,
                            spec.peak_input_bits, domains=space.domains)
        want, got = ref.metrics(batch.matrix)[0], jx.metrics(batch.matrix)[0]
        rel = np.abs(got - want) / np.maximum(np.abs(want), 1e-30)
        assert float(rel.max()) <= 1e-6, app
    counters = obs.metrics().counters
    assert counters["scorer.programs"] == 2
    assert counters["scorer.program_reuses"] == 0
    assert len(fresh_programs) == 2


def test_value_set_growth_rebuilds_the_program(space, fresh_programs):
    """Without `domains` the tables start from the defaults and grow
    with the pools: each growth re-uploads and, the value-set sizes
    having changed, builds a new program, within parity."""
    from repro.kernels.costmodel import FusedJaxScorer
    spec = AppSpec.from_graph("resnet", apps.build_app("resnet"))
    kw = dict(peak_weight_bits=spec.peak_weight_bits,
              peak_input_bits=spec.peak_input_bits)
    rng = np.random.default_rng(15)
    batch = space.decode_batch(space.sample_indices(rng, 200))
    jx = FusedJaxScorer(spec.stream, space.hw, **kw)
    rebuilds = jx.t.n_rebuilds
    jx.metrics(batch.matrix[:5])
    got = jx.metrics(batch.matrix)[0]
    assert jx.t.n_rebuilds >= rebuilds + 2
    ref = FusedStreamScorer(spec.stream, space.hw, **kw)
    want = ref.metrics(batch.matrix)[0]
    rel = np.abs(got - want) / np.maximum(np.abs(want), 1e-30)
    assert float(rel.max()) <= 1e-6
    assert obs.metrics().counters["scorer.programs"] == 2
    assert [(a["upload"], a["reused"]) for a in _program_spans()] == \
        [(True, False), (True, False)]


def program_shapes(scorer, bucket):
    """The scorer program's arguments as shapes, and its static `nvals`."""
    import jax
    from repro.core.costmodel import _FAST_FIELDS
    from repro.kernels.costmodel import _COL_FIELDS
    app, nvals = scorer._app_args()
    pool = tuple(np.zeros((bucket, len(f)), dtype=np.int64)
                 for f in (_FAST_FIELDS, _COL_FIELDS))
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype),
        (*app, *pool)), nvals


def test_scorer_program_keeps_the_name_the_benchmark_reads(resnet_spec,
                                                           space):
    """The benchmark finds the scorer kernel in a device trace by the
    name `fused_jax_score` (`bench/harness.py` `SCORER_PROGRAM`); a
    rename must fail here, not silently empty its kernel metrics."""
    jax = pytest.importorskip("jax")
    from repro.kernels.costmodel import FusedJaxScorer, _fused_jit
    jx = FusedJaxScorer(resnet_spec.stream, space.hw,
                        resnet_spec.peak_weight_bits,
                        resnet_spec.peak_input_bits, domains=space.domains)
    with jax.enable_x64(True):
        args, nvals = program_shapes(jx, 256)
        text = _fused_jit.lower(*args, nvals=nvals).as_text()
    assert "fused_jax_score" in text
