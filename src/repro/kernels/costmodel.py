"""Fused jax scorer for the Table-1 analytical cost model.

`FusedJaxScorer` is the `backend="jax"` twin of
`repro.core.costmodel.FusedStreamScorer`, and the only device
implementation of the model's equations: the same hoisted per-(value,
op) gather tables, uploaded to the device once per table build and
passed, with the op-stream constants, as arguments to `fused_jax_score`.
Per call the host does the cheap LUT coding of the pool matrix and the
O(N) area polynomial (`area_many`, so areas are bit-identical to numpy
whatever the device's 64-bit float arithmetic); the [C, O] work — the
Eq. (9)-(13) validity screen and the Eq. (1)-(8) latency tail — runs
device-side in a single fused XLA program.

Pool sizes are padded up to buckets (powers of two) so steady-state
search rounds with ragged miss-set sizes reuse a handful of compiled
programs instead of recompiling per shape; padded rows score as invalid
and are sliced off.  A program is keyed by shape alone (the bucket, the
value-set sizes, the shape and dtype of every table), so it is compiled
(or loaded from the persistent cache) once per process and shared by
every scorer whose tables have those shapes: later `Study`s, and apps
of equal op count and value sets, only upload their tables.

`metrics` reports to `repro.obs`: spans `scorer.code` (LUT coding and
padding), `scorer.program` (the first time a scorer meets a bucket: a
table upload where its `upload` is true, then the program lookup, which
compiles unless `reused`), `scorer.run` (dispatch, copies, the kernel
and the blocking readback) and `scorer.area`; counters `scorer.rows`,
`scorer.rows_padded`, `scorer.cells` (padded rows x unique op columns:
the [rows, columns] extent of the kernel's gathers), `scorer.programs`
(programs compiled) and `scorer.program_reuses` (lookups that found one).
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.costmodel import (ConfigBatch, HardwareConstants, LoopOrder,
                                  OpStream, _FAST_FIELDS, _fused_tables_for,
                                  area_many)
from repro.core.costmodel import FusedStreamScorer as _NumpyScorer

__all__ = ["FusedJaxScorer"]

_COL_FIELDS = ("loop_order", "pe_group", "mac_per_group", "bank_height",
               "bank_width", "weight_banks_pg", "act_banks_pg")

_TABLES = ("pb_tbl", "ifp_tbl", "ofp_tbl", "xp_tbl", "yp_tbl", "kk_tbl",
           "win_x_tbl", "win_y_tbl", "wt_tbl", "spatial_tbl", "u1_tbl",
           "u2_tbl", "u3_tbl", "atile_tbl", "num_weight", "num_input",
           "ws_weight", "ie_batch", "is_input", "weight_elems", "repeat")

_MIN_BUCKET = 256
# compiled programs kept per process (a paper-cnn7 pass needs 14)
_MAX_PROGRAMS = 64


def _bucket(n: int) -> int:
    b = _MIN_BUCKET
    while b < n:
        b <<= 1
    return b


def fused_jax_score(dev, expand, scalars, codes, cols, *, nvals):
    """GOPS of every row of a padded pool: `dev` holds the device op
    tables (`_TABLES`), `expand` maps op-table columns to the stream's
    ops, `scalars` is (total_ops, peak weight bits, peak input bits x max
    batch, bit width, frequency) as 0-d arrays, `codes` and `cols` are the
    pool's [bucket, *] field codes and raw columns; `nvals` (static) is
    each fast field's value-set size, in `_FAST_FIELDS` order."""
    nv = dict(zip(_FAST_FIELDS, nvals))
    total_ops, pw, pi_scaled, bit_width, freq = scalars
    c = {f: codes[:, j] for j, f in enumerate(_FAST_FIELDS)}
    k = {f: cols[:, j] for j, f in enumerate(_COL_FIELDS)}

    pe_group = k["pe_group"]
    total_macs = pe_group * k["mac_per_group"]
    banks_w = k["weight_banks_pg"] * pe_group * k["bank_width"]
    banks_a = k["act_banks_pg"] * pe_group * k["bank_width"]
    wbuf = banks_w * k["bank_height"]
    abuf = banks_a * k["bank_height"]

    i_u1 = ((c["tif"] * nv["pif"] + c["pif"]) * nv["pkx"]
            + c["pkx"]) * nv["pky"] + c["pky"]
    i_u2 = ((c["tix"] * nv["pox"] + c["pox"]) * nv["tiy"]
            + c["tiy"]) * nv["poy"] + c["poy"]
    i_u3 = (c["tof"] * nv["pof"] + c["pof"]) * nv["pb"] + c["pb"]
    i_wt = c["tif"] * nv["tof"] + c["tof"]
    i_at = ((c["tix"] * nv["tiy"] + c["tiy"]) * nv["tif"]
            + c["tif"]) * nv["tof"] + c["tof"]

    # Eq. (9)-(13): validity screen over the joint op tables
    unroll = (dev["u1_tbl"][i_u1] * dev["u2_tbl"][i_u2]
              * dev["u3_tbl"][i_u3])
    valid_ops = unroll <= total_macs[:, None]
    valid_ops &= wbuf[:, None] >= dev["wt_tbl"][1][i_wt]
    valid_ops &= abuf[:, None] >= dev["atile_tbl"][i_at]
    valid = valid_ops.all(axis=1)
    # a zero peak adds no condition
    valid &= jnp.where(pw != 0, wbuf >= pw, True)
    valid &= jnp.where(pi_scaled != 0, abuf >= pi_scaled, True)

    # Eq. (1)-(8) latency tail (computed for every row; padding and
    # invalid rows are masked out of the GOPS at the end)
    g = dev["pb_tbl"][:, i_u3 % nv["pb"]]
    # pb code is the trailing radix of i_u3; recover it directly
    batch_iters, pb = g[0], g[1]
    g = dev["ifp_tbl"][:, c["tif"] * nv["pif"] + c["pif"]]
    cd_if, pif = g[0], g[1]
    g = dev["ofp_tbl"][:, c["tof"] * nv["pof"] + c["pof"]]
    cd_of, pof = g[0], g[1]
    i_xp = c["tix"] * nv["pox"] + c["pox"]
    g = dev["xp_tbl"][:, i_xp]
    cd_ox, pox = g[0], g[1]
    i_yp = c["tiy"] * nv["poy"] + c["poy"]
    g = dev["yp_tbl"][:, i_yp]
    cd_oy, poy = g[0], g[1]
    g = dev["kk_tbl"][:, c["pkx"] * nv["pky"] + c["pky"]]
    cd_kk, p_kxky = g[0], g[1]
    gw = dev["wt_tbl"][:, i_wt]
    chan_tiles, ofm_tiles = gw[0], gw[2]
    spatial_tiles = dev["spatial_tbl"][c["tix"] * nv["tiy"]
                                      + c["tiy"]]

    inter = chan_tiles * spatial_tiles
    inner = cd_if * cd_kk * cd_ox * cd_oy * cd_of
    compute_cycles = inter * inner * batch_iters * dev["repeat"]

    poxy = pox * poy
    weight_reuse = poxy * pb                            # Eq. (1)
    in_win = (dev["win_x_tbl"][i_xp * nv["pkx"] + c["pkx"]]
              * dev["win_y_tbl"][i_yp * nv["pky"] + c["pky"]])
    input_reuse = jnp.maximum(
        (pof * p_kxky * poxy) // jnp.maximum(in_win, 1),
        1)                                              # Eq. (2)

    lo = k["loop_order"][:, None]
    ws_in = (dev["ie_batch"] * ofm_tiles).astype(jnp.float64)
    osis_w = (dev["weight_elems"]
              * spatial_tiles).astype(jnp.float64)
    num_weight_eff = jnp.where(
        lo == int(LoopOrder.PAPER),
        dev["num_weight"] / jnp.maximum(weight_reuse, 1),
        jnp.where(lo == int(LoopOrder.WEIGHT_STATIONARY),
                  dev["ws_weight"], osis_w))
    num_input_eff = jnp.where(
        lo == int(LoopOrder.PAPER),
        dev["num_input"] / jnp.maximum(input_reuse, 1),
        jnp.where(lo == int(LoopOrder.INPUT_STATIONARY),
                  dev["is_input"], ws_in))

    wbw = jnp.maximum(banks_w // bit_width, 1)[:, None]
    abw = jnp.maximum(banks_a // bit_width, 1)[:, None]
    weight_cycles = jnp.ceil(num_weight_eff / wbw)      # Eq. (7)
    input_cycles = jnp.ceil(num_input_eff / abw)        # Eq. (8)
    total = jnp.maximum(compute_cycles.astype(jnp.float64),
                        jnp.maximum(weight_cycles, input_cycles))
    cycles = total[:, expand].sum(axis=1)

    seconds = cycles / freq
    gops = jnp.where(valid & (cycles > 0),
                     total_ops / jnp.maximum(seconds, 1e-30) / 1e9,
                     0.0)
    return gops


_fused_jit = jax.jit(fused_jax_score, static_argnames="nvals")
_PROGRAMS: "OrderedDict[tuple, jax.stages.Compiled]" = OrderedDict()
_PROGRAMS_LOCK = threading.Lock()


def _program_key(args, nvals) -> tuple:
    """What a compiled program depends on: shapes, dtypes, `nvals`, x64."""
    return (nvals, bool(jax.config.jax_enable_x64),
            tuple((a.shape, np.dtype(a.dtype).str)
                  for a in jax.tree_util.tree_leaves(args)))


def _program(key, args, nvals):
    """The process-wide compiled program for `key`, compiled from `args`
    on a miss; the least recently used is dropped past `_MAX_PROGRAMS`."""
    with _PROGRAMS_LOCK:
        exe = _PROGRAMS.get(key)
        if exe is None:
            exe = _PROGRAMS[key] = _fused_jit.lower(*args,
                                                    nvals=nvals).compile()
            if len(_PROGRAMS) > _MAX_PROGRAMS:
                _PROGRAMS.popitem(last=False)
        else:
            _PROGRAMS.move_to_end(key)
        return exe


class FusedJaxScorer:
    """Device-resident fused (GOPS, area) scorer, `metrics()`-compatible
    with `FusedStreamScorer` (GOPS within 1e-6 of the reference, held by
    `tests/test_fused_eval.py` and `tests/test_config_batch.py`)."""

    def __init__(self, stream: OpStream, hw: HardwareConstants,
                 peak_weight_bits: int = 0, peak_input_bits: int = 0,
                 domains: Optional[Dict[str, Sequence[int]]] = None):
        if not _NumpyScorer.supports(stream):
            raise ValueError("stream not supported by the fused scorer; "
                             "use performance_gops/area_many")
        self.hw = hw
        self.peak_weight_bits = int(peak_weight_bits)
        self.peak_input_bits = int(peak_input_bits)
        self.t = _fused_tables_for(stream, hw, domains)
        self._dev = None        # device copy of `_app_args()[0]`
        self._exe: Dict[int, object] = {}   # pool bucket -> executable
        self._built_rebuilds = -1

    # ---------------------------------------------------------- device prep
    def _app_args(self):
        """Everything of the program that belongs to the app, on the host:
        ((tables, expand, scalars), nvals).  A lazy value-set growth
        rebuild of the shared numpy tables changes them."""
        t = self.t
        scalars = (np.float64(float(t.total_ops)),
                   np.int64(self.peak_weight_bits),
                   np.int64(self.peak_input_bits * t.max_batch),
                   np.int64(self.hw.bit_width),
                   np.float64(self.hw.frequency_hz))
        tables = {name: np.asarray(getattr(t, name)) for name in _TABLES}
        return ((tables, np.asarray(t.expand), scalars),
                tuple(t.nvals[f] for f in _FAST_FIELDS))

    # -------------------------------------------------------------- scoring
    def metrics(self, matrix: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        n = matrix.shape[0]
        if n == 0:
            z = np.zeros(0, dtype=np.float64)
            return z, z.copy()
        m = _bucket(n)
        with jax.enable_x64(True):
            with obs.span("scorer.code", n=n, bucket=m):
                code = self.t.codes(matrix)  # may grow/rebuild the tables
                codes = np.zeros((m, len(_FAST_FIELDS)), dtype=np.int64)
                cols = np.zeros((m, len(_COL_FIELDS)), dtype=np.int64)
                for j, f in enumerate(_FAST_FIELDS):
                    codes[:n, j] = code[f]
                J = ConfigBatch._INDEX
                for j, f in enumerate(_COL_FIELDS):
                    cols[:n, j] = matrix[:, J[f]]
            upload = self._built_rebuilds != self.t.n_rebuilds
            exe = None if upload else self._exe.get(m)
            if exe is None:
                app, nvals = self._app_args()
                key = _program_key((*app, codes, cols), nvals)
                reused = key in _PROGRAMS
                with obs.span("scorer.program", bucket=m, upload=upload,
                              reused=reused):
                    if upload:
                        self._dev = jax.device_put(app)
                        self._exe = {}
                        self._built_rebuilds = self.t.n_rebuilds
                    exe = self._exe[m] = _program(
                        key, (*self._dev, codes, cols), nvals)
                obs.counter("scorer.programs", 0 if reused else 1)
                obs.counter("scorer.program_reuses", int(reused))
            obs.counter("scorer.rows", n)
            obs.counter("scorer.rows_padded", m)
            obs.counter("scorer.cells", m * len(self.t.ops))
            with obs.span("scorer.run", n=n, bucket=m):
                gops = np.asarray(exe(*self._dev, codes, cols))
        with obs.span("scorer.area", n=n):
            area = area_many(ConfigBatch(matrix), self.hw)
        return gops[:n].astype(np.float64), area
