"""Trace reduction on synthetic events and a synthetic xplane."""

import pytest

from bench import trace_reduce as tr


def test_union_of_overlapping_and_nested_intervals():
    ivs = [(0, 10), (5, 15), (20, 30), (22, 25), (30, 31), (40, 40)]
    assert tr.merge(ivs) == [(0.0, 15.0), (20.0, 31.0)]
    assert tr.union_length(ivs) == 26.0


def test_union_is_order_free_and_clipped():
    ivs = [(20, 30), (0, 10), (5, 15)]
    assert tr.union_length(ivs) == tr.union_length(reversed(ivs))
    assert tr.clip(ivs, 8, 25) == [(20, 25), (8, 10), (8, 15)]
    assert tr.union_length(tr.clip(ivs, 8, 25)) == 12.0


def test_gaps_cover_the_window_minus_busy():
    busy = [(2, 4), (3, 6), (8, 9)]
    assert tr.gaps(busy, 0, 10) == [(0, 2.0), (6.0, 8.0), (9.0, 10)]
    idle = sum(e - s for s, e in tr.gaps(busy, 0, 10))
    assert idle + tr.union_length(tr.clip(busy, 0, 10)) == 10
    assert tr.gaps([], 0, 5) == [(0, 5)]


def test_per_program_sum_counts_every_run_in_full():
    events = [("jit_fused_jax_score(1)", 0, 3), ("jit_other", 1, 2),
              ("jit_fused_jax_score(2)", 2, 6)]
    assert tr.sum_matching(events, "fused_jax_score") == (7.0, 2)
    assert tr.sum_matching(events, "absent") == (0.0, 0)


XSPACE = '''
planes {
  id: 1
  name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 6000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_fused_jax_score(7)" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 9500000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
}
planes {
  id: 3
  name: "Task Environment"
  stats { metadata_id: 1 uint64_value: 1700000000000000000 }
  stat_metadata { key: 1 value { id: 1 name: "profile_start_time" } }
}
'''


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData
    return tr.Trace(ProfileData.from_text_proto(XSPACE))


def test_trace_reads_device_host_and_clock(trace):
    assert trace.device_names == ["/device:TPU:0"]
    assert trace.start_epoch_ns == 1.7e18
    assert trace.annotation("bench.window") == (500.0, 10000.0)
    lo, hi = trace.annotation("bench.window")
    # ops at [1000, 3000], [2000, 4000], [7000, 8000]: 4000 ns busy
    assert trace.busy_s(lo, hi) == pytest.approx(4000e-9)
    assert trace.idle_gaps(lo, hi) == [(500.0, 1000.0), (4000.0, 7000.0),
                                       (8000.0, 10000.0)]
    assert trace.module_time_s("fused_jax_score", lo, hi) == \
        (pytest.approx(4000e-9), 2)
    top = trace.top_ops(lo, hi)
    assert [n for n, _ in top] == ["fusion.1", "fusion.2"]
    assert top[0][1] == pytest.approx(3000e-9)


def _span(name, ts, dur, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "args": args}


def test_span_self_time_and_idle_attribution():
    from bench import spans
    evs = [_span("study", 0, 100), _span("ask_tell_round", 10, 30),
           _span("evaluate_batch", 15, 10), _span("ask_tell_round", 50, 20),
           _span("evaluate_batch", 55, 5), _span("evaluate_batch", 80, 5),
           _span("search_app", 5, 70, app="resnet")]
    assert spans.self_us(evs, "ask_tell_round", "evaluate_batch") == 35.0
    idle = spans.attribute([(0, 12), (20, 30), (45, 58), (82, 110)], evs)
    # innermost pieces: study 0-5, search_app 5-10, round 10-15, batch
    # 15-25, round 25-40, search_app 40-50, round 50-55, batch 55-60, ...
    assert idle == {"study": 20.0, "search_app resnet": 10.0,
                    "ask_tell_round": 12.0, "evaluate_batch": 11.0,
                    "outside any span": 10.0}
