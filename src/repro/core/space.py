"""Accelerator design space (paper Table 2 + §2.2 unrolling variables).

A `DesignSpace` is an ordered mapping from design-variable name to its
discrete domain.  `sample()` draws a random valid starting configuration
(Algorithm 1 line 1); `neighbors_over()` enumerates one variable's domain
with all others fixed (Algorithm 1 lines 5-9).

The default space mirrors the paper's Table 2 plus the P* unrolling factors
of §2.2, with power-of-two domains as is standard for banked-SRAM/systolic
design points.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro import obs
from repro.core.costmodel import (AccelConfig, ConfigBatch,
                                  HardwareConstants, LoopOrder, area_many)

__all__ = ["DesignSpace", "default_space", "DEFAULT_AREA_BUDGET"]


def _pow2(lo: int, hi: int) -> Tuple[int, ...]:
    out = []
    v = lo
    while v <= hi:
        out.append(v)
        v *= 2
    return tuple(out)


@dataclasses.dataclass
class DesignSpace:
    """Discrete domains for every design variable of `AccelConfig`."""

    domains: Dict[str, Tuple[int, ...]]
    hw: HardwareConstants = dataclasses.field(default_factory=HardwareConstants)
    area_budget: float = 0.0

    @property
    def variables(self) -> List[str]:
        return list(self.domains.keys())

    def size(self) -> float:
        n = 1.0
        for d in self.domains.values():
            n *= len(d)
        return n

    def sample(self, rng: np.random.Generator,
               max_tries: int = 1000,
               validator=None) -> AccelConfig:
        """Random *valid* configuration (Algorithm 1 line 1).

        `validator(cfg) -> bool` may additionally enforce the Eq. 9-13
        application constraints so the greedy search never starts from a
        0-GOPS point.
        """
        for _ in range(max_tries):
            kwargs = {k: int(rng.choice(v)) for k, v in self.domains.items()}
            cfg = AccelConfig(**kwargs)
            if self.area_budget > 0 and cfg.area(self.hw) > self.area_budget:
                continue
            if validator is not None and not validator(cfg):
                continue
            return cfg
        raise RuntimeError("could not sample a valid configuration; loosen "
                           "the area budget or shrink the space")

    def neighbors_over(self, cfg: AccelConfig,
                       variable: str) -> List[AccelConfig]:
        """All configurations obtained by sweeping `variable` (others fixed)."""
        out = []
        for v in self.domains[variable]:
            out.append(dataclasses.replace(cfg, **{variable: int(v)}))
        return out

    # ------------------------------------------------ vectorized conversion
    def codec(self):
        """`SpaceCodec` for this space: vectorized config <-> index-array
        conversion so search engines manipulate populations as
        struct-of-arrays instead of lists of dataclasses."""
        from repro.core.search.base import SpaceCodec
        codec = getattr(self, "_codec", None)
        if codec is None or codec.domains != {k: tuple(v) for k, v
                                              in self.domains.items()}:
            codec = SpaceCodec(self.domains, AccelConfig)
            self._codec = codec
        return codec

    def encode(self, configs: Sequence[AccelConfig]) -> np.ndarray:
        """configs -> [N, V] int64 domain-index array (columns follow
        `self.variables` order)."""
        return self.codec().encode(configs)

    def decode(self, idx: np.ndarray) -> List[AccelConfig]:
        """[N, V] domain-index array -> AccelConfig list (encode inverse)."""
        return self.codec().decode(idx)

    def decode_batch(self, idx: np.ndarray) -> ConfigBatch:
        """[N, V] domain-index array -> array-native `ConfigBatch`, without
        materializing any dataclass (the engines' scoring fast path)."""
        return ConfigBatch.from_columns(**self.codec().decode_values(idx))

    def encode_batch(self, batch: ConfigBatch) -> np.ndarray:
        """`ConfigBatch` -> [N, V] domain-index array (decode_batch
        inverse; every field value must be a domain member)."""
        codec = self.codec()
        return codec.encode_values(
            {v: batch.col(v) for v in codec.variables})

    def sample_indices(self, rng: np.random.Generator,
                       n: int) -> np.ndarray:
        """Uniform random [n, V] index population (no validity filtering)."""
        return self.codec().sample_indices(rng, n)

    def within_area(self, cfg: AccelConfig) -> bool:
        return self.area_budget <= 0 or cfg.area(self.hw) <= self.area_budget

    def repair_for_peaks(self, cfg: AccelConfig, peak_weight_bits: int,
                         peak_input_bits: int) -> AccelConfig:
        """Minimal domain-respecting repair: grow buffer variables until the
        Eq. (11)/(13) peak-demand floors hold, then shrink compute variables
        until the area budget holds.  Keeps the rest of the random sample
        untouched (Algorithm 1 line 1 needs *a* valid point, not a good
        one)."""
        grow_w = ("bank_height", "weight_banks_pg", "bank_width", "pe_group")
        grow_a = ("bank_height", "act_banks_pg", "bank_width", "pe_group")

        def bump(c: AccelConfig, var: str) -> Optional[AccelConfig]:
            dom = sorted(self.domains[var])
            cur = getattr(c, var)
            bigger = [v for v in dom if v > cur]
            if not bigger:
                return None
            return dataclasses.replace(c, **{var: int(bigger[0])})

        for _ in range(64):
            if cfg.weight_buffer_bits() >= peak_weight_bits:
                break
            for var in grow_w:
                nxt = bump(cfg, var)
                if nxt is not None:
                    cfg = nxt
                    break
            else:
                break
        for _ in range(64):
            if cfg.act_buffer_bits() >= peak_input_bits:
                break
            for var in grow_a:
                nxt = bump(cfg, var)
                if nxt is not None:
                    cfg = nxt
                    break
            else:
                break
        # area repair: shrink compute/tiling first — never the bank
        # variables (that would re-break the buffer floors just grown)
        for var in ("mac_per_group", "tif", "tof"):
            while (self.area_budget > 0
                   and cfg.area(self.hw) > self.area_budget):
                dom = sorted(self.domains[var])
                cur = getattr(cfg, var)
                smaller = [v for v in dom if v < cur]
                if not smaller:
                    break
                cfg = dataclasses.replace(cfg, **{var: int(smaller[-1])})
        # still over budget: the SRAM dominates (oversized banks from a
        # random sample or a crossover/mutation product).  Shrink buffer
        # variables stepwise, but only accept a step that keeps both
        # Eq. 11/13 floors satisfied — repaired genetic offspring must
        # respect the floors AND the area budget simultaneously.
        shrink_bufs = ("bank_height", "act_banks_pg", "weight_banks_pg",
                       "bank_width", "pe_group")
        for _ in range(64):
            if (self.area_budget <= 0
                    or cfg.area(self.hw) <= self.area_budget):
                break
            for var in shrink_bufs:
                dom = sorted(self.domains[var])
                cur = getattr(cfg, var)
                smaller = [v for v in dom if v < cur]
                if not smaller:
                    continue
                cand = dataclasses.replace(cfg, **{var: int(smaller[-1])})
                if (cand.weight_buffer_bits() >= peak_weight_bits
                        and cand.act_buffer_bits() >= peak_input_bits):
                    cfg = cand
                    break
            else:
                break
        return cfg

    # ------------------------------------------------- batched validity repair
    _GROW_W = ("bank_height", "weight_banks_pg", "bank_width", "pe_group")
    _GROW_A = ("bank_height", "act_banks_pg", "bank_width", "pe_group")
    _SHRINK_AREA = ("mac_per_group", "tif", "tof")
    _SHRINK_BUFS = ("bank_height", "act_banks_pg", "weight_banks_pg",
                    "bank_width", "pe_group")

    def _sorted_domain(self, var: str) -> np.ndarray:
        cache = getattr(self, "_sorted_domains", None)
        if cache is None:
            cache = self._sorted_domains = {}
        dom = cache.get(var)
        if dom is None or len(dom) != len(self.domains[var]):
            dom = cache[var] = np.asarray(sorted(self.domains[var]),
                                          dtype=np.int64)
        return dom

    def repair_for_peaks_many(self, configs, peak_weight_bits: int,
                              peak_input_bits: int) -> ConfigBatch:
        """Vectorized `repair_for_peaks` over a whole population.

        Row `i` of the result equals
        ``repair_for_peaks(configs[i], peak_weight_bits, peak_input_bits)``
        exactly.  Phases A/B grow the buffers in closed form, a few numpy
        passes per variable over only the rows still under their floor
        (`_grow_many`); phases C/D run the scalar shrink schedule step by
        step, but only on the rows over the area budget after growth
        (`_shrink_many`), written back once.  Accepts a `ConfigBatch` or
        any `AccelConfig` sequence; returns a new `ConfigBatch` (inputs
        are never mutated)."""
        m = ConfigBatch.from_configs(configs).matrix.copy()
        n = m.shape[0]
        with obs.span("space.repair", n=n):
            grown = self._grow_many(m, self._GROW_W, "weight_banks_pg",
                                    peak_weight_bits)
            grown |= self._grow_many(m, self._GROW_A, "act_banks_pg",
                                     peak_input_bits)
            over = np.zeros(0, dtype=np.int64)
            if self.area_budget > 0:
                over = np.flatnonzero(area_many(ConfigBatch(m), self.hw)
                                      > self.area_budget)
                if len(over):
                    sub = m[over]
                    self._shrink_many(sub, peak_weight_bits, peak_input_bits)
                    m[over] = sub
            obs.counter("repair.rows", n)
            obs.counter("repair.rows_grown", int(grown.sum()))
            obs.counter("repair.rows_over_area", len(over))
        return ConfigBatch(m)

    def _grow_many(self, m: np.ndarray, grow_vars: Sequence[str],
                   banks: str, floor: int) -> np.ndarray:
        """Phases A/B of `repair_for_peaks` on `m` in place; returns the
        bool[N] mask of rows moved.

        The scalar schedule bumps the first growable variable one domain
        step at a time, so a variable rises until the floor holds or it
        reaches its maximum before the next one moves: its final value is
        the first domain value >= ceil(floor / rest), `rest` being the
        product of the other buffer factors, clipped to the domain's
        maximum.  A row's bumps are counted as domain positions and stop
        at the scalar loop's 64th."""
        j_of = ConfigBatch._INDEX
        factors = [j_of[v] for v in (banks, "pe_group", "bank_height",
                                     "bank_width")]
        moved = np.zeros(m.shape[0], dtype=bool)
        rows = np.flatnonzero(
            m[:, factors[0]] * m[:, factors[1]] * m[:, factors[2]]
            * m[:, factors[3]] < floor)
        cols = [m[rows, f] for f in factors]     # copies, never views of m
        left = np.full(len(rows), 64, dtype=np.int64)
        big = np.iinfo(np.int64).max
        floor64 = min(floor, big)
        for var in grow_vars:
            if not len(rows):
                break
            k, dom = factors.index(j_of[var]), self._sorted_domain(var)
            others = [c for i, c in enumerate(cols) if i != k]
            rest = others[0] * others[1] * others[2]
            target = np.where(rest > 0, -(-floor64 // np.maximum(rest, 1)),
                              big)
            first = np.searchsorted(dom, cols[k], side="right")
            last = np.minimum(np.searchsorted(dom, target, side="left"),
                              len(dom) - 1)
            steps = np.minimum(np.maximum(last - first + 1, 0), left)
            step = np.flatnonzero(steps)
            cols[k][step] = dom[first[step] + steps[step] - 1]
            m[rows[step], j_of[var]] = cols[k][step]
            moved[rows[step]] = True
            left -= steps
            keep = np.flatnonzero((rest * cols[k] < floor) & (left > 0))
            rows, left = rows[keep], left[keep]
            cols = [c[keep] for c in cols]
        return moved

    def _shrink_many(self, sub: np.ndarray, peak_weight_bits: int,
                     peak_input_bits: int) -> None:
        """Phases C/D of `repair_for_peaks`, in place on `sub`, the rows
        over the area budget: the scalar step schedule, one domain
        position per step, with `area_many` over this subset only."""
        j_of = ConfigBatch._INDEX
        budget = self.area_budget

        def over() -> np.ndarray:
            return area_many(ConfigBatch(sub), self.hw) > budget

        # phase C: shrink compute/tiling variables while over the budget
        for var in self._SHRINK_AREA:
            j, dom = j_of[var], self._sorted_domain(var)
            pos = np.searchsorted(dom, sub[:, j], side="left")
            for _ in range(len(dom)):
                sel = over() & (pos > 0)
                if not sel.any():
                    break
                pos[sel] -= 1
                sub[sel, j] = dom[pos[sel]]

        # phase D: shrink buffer variables stepwise, accepting only steps
        # that keep both Eq. 11/13 floors satisfied; a row with no such
        # step leaves for good (the scalar `break`)
        active = np.ones(len(sub), dtype=bool)
        for _ in range(64):
            active &= over()
            if not active.any():
                break
            changed = np.zeros(len(sub), dtype=bool)
            for var in self._SHRINK_BUFS:
                j, dom = j_of[var], self._sorted_domain(var)
                pos = np.searchsorted(dom, sub[:, j], side="left")
                sel = np.flatnonzero(active & ~changed & (pos > 0))
                if not len(sel):
                    continue
                cand = ConfigBatch(sub[sel])
                cand.matrix[:, j] = dom[pos[sel] - 1]
                ok = ((cand.weight_buffer_bits_arr() >= peak_weight_bits)
                      & (cand.act_buffer_bits_arr() >= peak_input_bits))
                sub[sel[ok], j] = cand.matrix[ok, j]
                changed[sel[ok]] = True
            active &= changed


# A representative area budget: room for ~16K MACs plus ~tens of Mbit of
# banked SRAM plus control — large enough that the big-peak applications
# (fasterRCNN, deeplab) are feasible at all, small enough that their memory
# lower bounds (Eqs. 10-13) kill many configurations (the paper's dense
# 0-GOPS lines in Fig. 7(b)/(d)) and compute/memory trade-offs are real.
DEFAULT_AREA_BUDGET = 90000.0


def default_space(hw: Optional[HardwareConstants] = None,
                  area_budget: float = DEFAULT_AREA_BUDGET) -> DesignSpace:
    """The paper-shaped design space (Table 2 variables + P* unrolling)."""
    hw = hw or HardwareConstants()
    domains: Dict[str, Tuple[int, ...]] = {
        "loop_order": tuple(int(v) for v in LoopOrder),
        "pe_group": _pow2(1, 64),
        "mac_per_group": _pow2(16, 512),
        "bank_height": _pow2(256, 8192),
        "bank_width": (16, 32, 64, 128),
        "weight_banks_pg": _pow2(1, 16),
        "act_banks_pg": _pow2(1, 16),
        "tif": _pow2(4, 512),
        "tix": _pow2(8, 256),
        "tiy": _pow2(8, 256),
        "tof": _pow2(4, 512),
        "pif": _pow2(1, 64),
        "pof": _pow2(1, 64),
        "pox": _pow2(1, 16),
        "poy": _pow2(1, 16),
        "pkx": (1, 3, 5, 7),
        "pky": (1, 3, 5, 7),
        "pb": _pow2(1, 16),
    }
    return DesignSpace(domains=domains, hw=hw, area_budget=area_budget)
