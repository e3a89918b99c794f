"""Reduce a JAX profiler trace (`*.xplane.pb`) to device metrics.

Planes, as a TPU trace names them: one `/device:TPU:<i>` plane per chip,
whose `XLA Ops` line holds one event per HLO operation run on the chip and
whose `XLA Modules` line holds one event per program execution (named
after the jitted function, e.g. `jit_fused_jax_score(...)`); host planes
(`/host:CPU`) hold `jax.profiler.TraceAnnotation` events; the `Task
Environment` plane's `profile_start_time` stat is the epoch time, in ns,
that event offsets count from.

Everything here works on plain `(start_ns, end_ns)` intervals and
`(name, start_ns, end_ns)` events, so it can be checked on synthetic
event lists.
"""

from __future__ import annotations

import collections
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
Event = Tuple[str, float, float]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


# ------------------------------------------------------------ intervals
def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as sorted, disjoint intervals."""
    out: List[Interval] = []
    for s, e in sorted((float(s), float(e)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def union_length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in merge(intervals))


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Idle intervals of [lo, hi] not covered by `busy`."""
    out: List[Interval] = []
    t = lo
    for s, e in merge(clip(busy, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def short_op_name(hlo_text: str) -> str:
    """`%fusion.10 = u32[16384] fusion(...), kind=kLoop` -> `fusion.10`."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def sum_matching(events: Iterable[Event], needle: str) -> Tuple[float, int]:
    """(total duration, count) of the events whose name contains
    `needle`."""
    total, n = 0.0, 0
    for name, s, e in events:
        if needle in name:
            total += e - s
            n += 1
    return total, n


# ------------------------------------------------------------ xplane I/O
def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    return paths[-1]


def _events(line) -> List[Event]:
    return [(ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
            for ev in line.events]


class Trace:
    """The parts of one profiler trace the benchmark reads."""

    def __init__(self, profile_data) -> None:
        self.device_ops: List[List[Event]] = []      # per device plane
        self.device_modules: List[List[Event]] = []
        self.device_names: List[str] = []
        self.host: List[Event] = []
        self.start_epoch_ns: Optional[float] = None
        for plane in profile_data.planes:
            lines = {line.name: line for line in plane.lines}
            if plane.name.startswith("/device:") and OPS_LINE in lines:
                self.device_names.append(plane.name)
                self.device_ops.append(_events(lines[OPS_LINE]))
                self.device_modules.append(
                    _events(lines[MODULES_LINE]) if MODULES_LINE in lines
                    else [])
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    self.host.extend(_events(line))
            for key, value in plane.stats:
                if key == "profile_start_time":
                    self.start_epoch_ns = float(value)

    @classmethod
    def from_dir(cls, log_dir: str) -> "Trace":
        from jax.profiler import ProfileData
        return cls(ProfileData.from_file(find_xplane(log_dir)))

    def annotation(self, name: str) -> Optional[Interval]:
        """Span of the first host event called `name`."""
        for ev, s, e in self.host:
            if ev == name:
                return (s, e)
        return None

    def busy_s(self, lo: float, hi: float) -> float:
        """Seconds within [lo, hi] (ns) in which an operation ran on the
        device, averaged over the device planes."""
        if not self.device_ops:
            return 0.0
        per = [union_length(clip([(s, e) for _, s, e in ops], lo, hi))
               for ops in self.device_ops]
        return sum(per) / len(per) / 1e9

    def idle_gaps(self, lo: float, hi: float) -> List[Interval]:
        """Gaps (ns) within [lo, hi] in which the first device ran
        nothing."""
        ops = self.device_ops[0] if self.device_ops else []
        return gaps([(s, e) for _, s, e in ops], lo, hi)

    def module_time_s(self, needle: str, lo: float, hi: float
                      ) -> Tuple[float, int]:
        """(device seconds, executions) of the programs whose name
        contains `needle`, summed over devices, within [lo, hi]."""
        total, n = 0.0, 0
        for mods in self.device_modules:
            t, k = sum_matching([(m, s, e) for m, s, e in mods
                                 if s >= lo and e <= hi], needle)
            total += t
            n += k
        return total / 1e9, n

    def top_ops(self, lo: float, hi: float, k: int = 10
                ) -> List[Tuple[str, float]]:
        """The `k` device operations that took the most time, in s, by
        HLO instruction name (the trace names an op by its whole HLO
        text, `%fusion.10 = u32[...] fusion(...), ...`)."""
        totals: Dict[str, float] = collections.defaultdict(float)
        for ops in self.device_ops:
            for name, s, e in ops:
                if s >= lo and e <= hi:
                    totals[short_op_name(name)] += (e - s) / 1e9
        return sorted(totals.items(), key=lambda kv: -kv[1])[:k]
