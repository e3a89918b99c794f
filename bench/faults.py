"""Faults planted in the device scorer (`FusedJaxScorer.metrics`), to show
that the comparison catches each fault a study cell can have:

- `answer_altered`: one answer changed by one part in a million where it
  is produced;
- `half_left_out`: only the first half of each batch scored, the rest
  answered with zeros;
- `state_unchanged`: a call returns the answers stored from an earlier
  call at least as large (a scorer whose state never moves on).

A single chip has no exchange between chips to leave out.  Used by
`bench/tests/test_faults.py` on the CPU and by `bench/control.py` on the
chip, never by a benchmark run.
"""

from __future__ import annotations

import numpy as np


def answer_altered(orig):
    def metrics(self, matrix):
        gops, area = orig(self, matrix)
        gops = gops.copy()
        gops[np.argmax(gops)] *= 1 + 1e-6
        return gops, area
    return metrics


def half_left_out(orig):
    def metrics(self, matrix):
        n = matrix.shape[0]
        gops, area = orig(self, matrix[: max(n // 2, 1)])
        out = np.zeros(n)
        out[: len(gops)] = gops
        return out, np.resize(area, n)
    return metrics


def state_unchanged(orig):
    state = {}

    def metrics(self, matrix):
        prev = state.get(id(self))
        if prev is not None and len(prev[0]) >= matrix.shape[0]:
            n = matrix.shape[0]
            return prev[0][:n], prev[1][:n]
        state[id(self)] = orig(self, matrix)
        return state[id(self)]
    return metrics


FAULTS = {f.__name__: f for f in (answer_altered, half_left_out,
                                  state_unchanged)}


def install(name: str, setattr_=setattr):
    """Plant fault `name`; returns what undoes it."""
    from repro.kernels.costmodel import FusedJaxScorer
    orig = FusedJaxScorer.metrics
    setattr_(FusedJaxScorer, "metrics", FAULTS[name](orig))
    return lambda: setattr_(FusedJaxScorer, "metrics", orig)
