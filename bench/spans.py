"""Host spans recorded by `repro.obs` (Chrome "X" events: epoch-us `ts`,
`dur` in us), reduced to per-layer times."""

from __future__ import annotations

import bisect
from typing import Dict, Iterable, List, Optional, Sequence, Tuple


def named(spans: Iterable[Dict], name: str) -> List[Dict]:
    return [s for s in spans if s.get("ph") == "X" and s.get("name") == name]


def total_us(spans: Iterable[Dict]) -> float:
    return float(sum(s["dur"] for s in spans))


def inside(outer: Dict, inner: Dict) -> bool:
    return (inner["ts"] >= outer["ts"]
            and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"])


def contained_us(outers: Sequence[Dict], inners: Sequence[Dict]) -> float:
    """Total duration of the `inners` that lie within one of `outers`
    (spans of one name follow each other and do not overlap)."""
    outers = sorted(outers, key=lambda s: s["ts"])
    starts = [o["ts"] for o in outers]
    total = 0
    for i in inners:
        k = bisect.bisect_right(starts, i["ts"]) - 1
        if k >= 0 and inside(outers[k], i):
            total += i["dur"]
    return float(total)


def self_us(spans: Sequence[Dict], parent: str, child: str) -> float:
    """Time in `parent` spans not covered by the `child` spans inside
    them."""
    outers = named(spans, parent)
    return total_us(outers) - contained_us(outers, named(spans, child))


def segments(spans: Sequence[Dict]) -> List[Tuple[float, float, Dict]]:
    """The timeline cut where any span opens or closes, each piece with
    the innermost span open over it (epoch us).  Spans of one thread
    nest, so the open ones form a stack."""
    bounds = []
    for s in spans:
        if s.get("ph") == "X":
            bounds.append((s["ts"], 1, -s["dur"], id(s), s))
            bounds.append((s["ts"] + s["dur"], 0, 0, id(s), s))
    bounds.sort(key=lambda b: b[:4])
    out: List[Tuple[float, float, Dict]] = []
    stack: List[Dict] = []
    t = None
    for when, opening, _, _, s in bounds:
        if stack and t is not None and when > t:
            out.append((t, when, stack[-1]))
        t = when
        if opening:
            stack.append(s)
        elif s in stack:
            stack.remove(s)
    return out


def attribute(intervals: Sequence[Tuple[float, float]],
              spans: Sequence[Dict]) -> Dict[str, float]:
    """Length of `intervals` (epoch us) covered by each innermost span's
    label; what no span covers goes to "outside any span"."""
    segs = segments(spans)
    out: Dict[str, float] = {}
    k = 0
    for lo, hi in sorted(intervals):
        covered = 0.0
        while k < len(segs) and segs[k][1] <= lo:
            k += 1
        j = k
        while j < len(segs) and segs[j][0] < hi:
            a, b = max(lo, segs[j][0]), min(hi, segs[j][1])
            if b > a:
                key = label(segs[j][2])
                out[key] = out.get(key, 0.0) + (b - a)
                covered += b - a
            j += 1
        if hi - lo > covered:
            out["outside any span"] = (out.get("outside any span", 0.0)
                                       + (hi - lo - covered))
    return out


def label(span: Optional[Dict]) -> str:
    if span is None:
        return "outside any span"
    app = span.get("args", {}).get("app")
    return f"{span['name']} {app}" if app else span["name"]
