"""The comparison that decides `correct`.

What a study produced on the timed path is compared with the plain
reference (`reference.py`) run on the host over the same op streams:

- `gops_gap`, `area_gap`: the scorer layer.  Every distinct configuration
  a study scored, with the (GOPS, area) the device scorer returned for it
  (read back from the study's row cache), against the reference's, as the
  widest relative gap |program - reference| / max(|program|, |reference|).
- `best_gap`: each app's reported best GOPS against the reference's best
  over everything that app's search evaluated (studies whose engines
  maximise GOPS, i.e. every objective but `pareto`).
- `select_gap`: the study's outcome.  For `geomean`, the §5.1 selection
  (top `top_frac` of each app's evaluated log, cross-evaluated on every
  app, best geometric mean) redone with reference numbers, against the
  study's `best_score`.  For `pareto`, the best geometric-mean GOPS within
  each area budget among each app's local front and incumbent, against
  the study's per-budget selection.
- `studies_differ`: window studies whose outcome differs from the checked
  study of the same seed.
- `stream_drift`: apps whose op stream, as the study scored it, differs
  from the one the configuration file records (op count, a digest of the
  [11, ops] op table, the Eq. 11/13 peak bits): a program change that
  moved what is scored moves both sides of the comparison together, so
  it has to show here.
"""

from __future__ import annotations

import hashlib
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench import reference

GapRow = Tuple[str, float, float, bool]     # name, value, limit, ok


def rel_gap(prog: np.ndarray, ref: np.ndarray) -> float:
    """Widest |prog - ref| / max(|prog|, |ref|); 0 where both are 0."""
    prog = np.asarray(prog, dtype=np.float64)
    ref = np.asarray(ref, dtype=np.float64)
    if prog.size == 0:
        return 0.0
    scale = np.maximum(np.abs(prog), np.abs(ref))
    gap = np.where(scale > 0, np.abs(prog - ref) / np.where(scale > 0,
                                                             scale, 1.0),
                   0.0)
    gap = np.where(np.isfinite(gap), gap, 1.0)
    return float(gap.max())


def _columns(matrix: np.ndarray, fields: Sequence[str]
             ) -> Dict[str, np.ndarray]:
    return {f: matrix[:, j] for j, f in enumerate(fields)}


def _ops_of(stream) -> Dict[str, np.ndarray]:
    return {f: np.asarray(getattr(stream, f)).ravel()
            for f in reference.OP_FIELDS}


def stream_signature(spec) -> Dict[str, Any]:
    """What one app's scorer is fed: its op count, a digest of its
    [11, ops] op table, and its peak bits."""
    table = np.stack([np.asarray(col, dtype=np.int64)
                      for col in _ops_of(spec.stream).values()])
    return {"ops": int(table.shape[1]),
            "op_table_sha256": hashlib.sha256(table.tobytes()).hexdigest(),
            "peak_weight_bits": int(spec.peak_weight_bits),
            "peak_input_bits": int(spec.peak_input_bits)}


class AppData:
    """One app's scored rows: the program's raw metrics and the
    reference's, plus the evaluated log as row indices."""

    def __init__(self, name, spec, res, fields, hw):
        from repro.core.costmodel import ConfigBatch
        self.name = name
        exported = res.evaluator.cache_export()
        ncol = len(fields)
        keyed = np.frombuffer(b"".join(exported.keys()),
                              dtype=np.int64).reshape(len(exported), ncol)
        vals = np.asarray(list(exported.values()),
                          dtype=np.float64).reshape(len(exported), -1)
        log = (ConfigBatch.from_configs(res.evaluated).matrix
               if res.evaluated else np.zeros((0, ncol), dtype=np.int64))
        rows, inv = np.unique(np.vstack([keyed, log]), axis=0,
                              return_inverse=True)
        inv = np.asarray(inv).ravel()
        self.rows = rows
        self.fields = fields
        self.log = inv[len(keyed):]
        self.prog_gops = np.full(len(rows), np.nan)
        self.prog_area = np.full(len(rows), np.nan)
        self.prog_gops[inv[:len(keyed)]] = vals[:, 0]
        self.prog_area[inv[:len(keyed)]] = vals[:, 1]
        self.ops = _ops_of(spec.stream)
        self.peaks = (int(spec.peak_weight_bits), int(spec.peak_input_bits))
        self.gops = reference.gops(_columns(rows, fields), self.ops, hw,
                                   *self.peaks)
        self.area = reference.area(_columns(rows, fields), hw)

    def row_of(self, cfg: Dict[str, int]) -> np.ndarray:
        return np.asarray([int(cfg[f]) for f in self.fields],
                          dtype=np.int64)


def _cross(apps: List[AppData], cand_rows: np.ndarray, hw) -> np.ndarray:
    """[apps, candidates] reference GOPS of candidate rows on every app."""
    cols = _columns(cand_rows, apps[0].fields)
    return np.stack([reference.gops(cols, a.ops, hw, *a.peaks)
                     for a in apps])


def _geomean_selection(apps: List[AppData], hw, budget: float,
                       top_frac: float, cap: int) -> float:
    cands = []
    for a in apps:
        perf = np.where(a.area[a.log] <= budget, a.gops[a.log], 0.0)
        valid = perf > 0
        if valid.any():
            thresh = np.quantile(perf[valid], 1.0 - top_frac)
            idx = np.flatnonzero(perf >= thresh)
        else:
            idx = np.asarray([int(np.argmax(perf))])
        # the Study's own ordering (same sort), so that tied candidates
        # fall on the same side of the cap
        order = idx[np.argsort(-perf[idx])]
        seen = set()
        for j in order:
            r = int(a.log[j])
            if r not in seen:
                seen.add(r)
                cands.append(a.rows[r])
            if len(seen) >= cap:
                break
    cross = _cross(apps, np.asarray(cands), hw)
    geo = np.where((cross > 0).all(axis=0), reference.geomean(cross), 0.0)
    return float(geo.max())


def _local_front(perf: np.ndarray, area: np.ndarray) -> List[int]:
    cand = np.flatnonzero(perf > 0)
    order = cand[np.lexsort((-perf[cand], area[cand]))]
    front, best = [], -np.inf
    for i in order:
        if perf[i] > best:
            front.append(int(i))
            best = perf[i]
    return front


def _pareto_selection(apps: List[AppData], result, hw, search_budget: float,
                      budgets: Sequence[float], cap: int
                      ) -> Dict[float, Optional[float]]:
    seen, cands = set(), []

    def add(row: np.ndarray) -> None:
        key = row.tobytes()
        if key not in seen:
            seen.add(key)
            cands.append(row)

    for a in apps:
        best = result.per_app[a.name].get("best")
        if best is not None:
            add(a.row_of(best))
        perf = np.where(a.area[a.log] <= search_budget, a.gops[a.log], 0.0)
        for j in _local_front(perf, a.area[a.log])[:cap]:
            add(a.rows[a.log[j]])
    rows = np.asarray(cands)
    cross = _cross(apps, rows, hw)
    score = np.where((cross > 0).all(axis=0), reference.geomean(cross), 0.0)
    areas = reference.area(_columns(rows, apps[0].fields), hw)
    out: Dict[float, Optional[float]] = {}
    for b in budgets:
        ok = (areas <= b) & (score > 0)
        out[b] = float(score[ok].max()) if ok.any() else None
    return out


def compare(study, result, config: Dict, traffic: Dict,
            studies_differ: int) -> List[Tuple[str, float]]:
    """The numbers compared for one study, as (name, value)."""
    from repro.core.costmodel import ConfigBatch
    hw = config["hw"]
    fields = tuple(ConfigBatch.FIELDS)
    apps = [AppData(s.name, s, result.per_app_results[s.name], fields, hw)
            for s in study.specs]
    scored = [np.isfinite(a.prog_gops) for a in apps]
    out = [("gops_gap", max(rel_gap(a.prog_gops[m], a.gops[m])
                            for a, m in zip(apps, scored))),
           ("area_gap", max(rel_gap(a.prog_area[m], a.area[m])
                            for a, m in zip(apps, scored)))]
    budget = float(config["area_budget"])
    cap = int(traffic["max_candidates_per_app"])
    if traffic["objective"] == "pareto":
        budgets = [float(b) for b in traffic["budgets"]]
        search_budget = max(budgets + [budget])
        ref = _pareto_selection(apps, result, hw, search_budget, budgets,
                                cap)
        gap = 0.0
        for b in budgets:
            pick = (result.budget_selections or {}).get(f"{b:g}")
            prog = None if pick is None else float(pick["score"])
            if (prog is None) != (ref[b] is None):
                gap = 1.0
            elif prog is not None:
                gap = max(gap, rel_gap(np.asarray([prog]),
                                       np.asarray([ref[b]])))
        out.append(("select_gap", gap))
    else:
        best = []
        for a in apps:
            perf = np.where(a.area <= budget, a.gops, 0.0)
            best.append(rel_gap(
                np.asarray([result.per_app[a.name]["best_perf"]]),
                np.asarray([perf.max() if perf.size else 0.0])))
        out.append(("best_gap", max(best)))
        if traffic["objective"] == "geomean":
            ref = _geomean_selection(apps, hw, budget,
                                     float(traffic["top_frac"]), cap)
            out.append(("select_gap", rel_gap(
                np.asarray([result.best_score]), np.asarray([ref]))))
    out.append(("studies_differ", float(studies_differ)))
    recorded = config["streams"]
    out.append(("stream_drift", float(sum(
        stream_signature(s) != recorded.get(s.name) for s in study.specs))))
    return out


def worst(per_study: Sequence[Sequence[Tuple[str, float]]]
          ) -> List[Tuple[str, float]]:
    """The largest value of each number over the checked studies; one
    that is not finite stays, so that it fails."""
    out: Dict[str, float] = {}
    for values in per_study:
        for name, value in values:
            kept = out.get(name, value)
            out[name] = (kept if not math.isfinite(kept)
                         else value if not math.isfinite(value)
                         else max(kept, value))
    return list(out.items())


def judge(values: Sequence[Tuple[str, float]], limits: Dict[str, float]
          ) -> Tuple[bool, List[GapRow]]:
    """(every value within its limit, rows of name/value/limit/ok).  A
    number without a limit, or one that is not finite, fails."""
    rows: List[GapRow] = []
    for name, value in values:
        limit = limits.get(name)
        ok = limit is not None and math.isfinite(value) and value <= limit
        rows.append((name, value, limit, ok))
    return all(r[3] for r in rows), rows
