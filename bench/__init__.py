"""Benchmark of `repro.dse.Study` on one chip; see PERF.md."""
