"""Plain reference for the analytical cost model (arXiv:1903.07676, §3).

A stand-alone copy of the verbatim Eq. (1)-(13) broadcast formulas and of
the §4.3 unit-area model.  It imports nothing of the program under test:
configurations come in as named integer columns, op streams as named
integer loop-bound rows, hardware constants as a plain dict.

`float_dtype` is the precision of every floating-point step (the Eq. 5-8
fetch volumes and divisions, the latency sum and GOPS, and the area
products).  float64 is the reference; float32 is the lower-precision
control that the benchmark's comparison has to reject.

Only the op columns that differ are costed: a stream's repeated layers
(transformer blocks, ResNet stages) are one column with a multiplicity, and
the per-config latency is the multiplicity-weighted sum over them.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

# Design variables of Table 2 (+ the P* unrolling factors of §2.2).
CONFIG_FIELDS = ("loop_order", "pe_group", "mac_per_group", "bank_height",
                 "bank_width", "weight_banks_pg", "act_banks_pg",
                 "tif", "tix", "tiy", "tof",
                 "pif", "pof", "pox", "poy", "pkx", "pky", "pb")

# Canonical loop bounds of one operation (Table 1 embedding) plus the
# batch size and the number of logical instances it stands for.
OP_FIELDS = ("nif", "nix", "niy", "nkx", "nky", "nof", "nox", "noy", "s",
             "batch", "repeat")

# Loop orders: PAPER is Eqs. (5)-(8) verbatim; the other three are the
# stationary dataflows' refetch models.
PAPER, WEIGHT_STATIONARY, OUTPUT_STATIONARY, INPUT_STATIONARY = 0, 1, 2, 3

ROW_CHUNK = 8192


def _ceil_div(a, b):
    return -(-a // np.maximum(b, 1))


def unique_ops(ops: Dict[str, np.ndarray]
               ) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
    """({field: [1, U]} distinct op columns, [U] multiplicities)."""
    table = np.stack([np.asarray(ops[f], dtype=np.int64).ravel()
                      for f in OP_FIELDS])
    cols, counts = np.unique(table, axis=1, return_counts=True)
    return ({f: cols[i][None, :] for i, f in enumerate(OP_FIELDS)},
            counts.astype(np.int64))


def total_ops(ops: Dict[str, np.ndarray]) -> int:
    """2 x sum of MACs x batch over the stream (1 MAC = 2 operations)."""
    o = {f: np.asarray(ops[f], dtype=np.int64).ravel() for f in OP_FIELDS}
    macs = (o["nif"] * o["nkx"] * o["nky"] * o["nox"] * o["noy"] * o["nof"]
            * o["repeat"])
    return 2 * int(sum(int(m) * int(b) for m, b in zip(macs, o["batch"])))


def _cycles_valid(c: Dict[str, np.ndarray], o: Dict[str, np.ndarray],
                  counts: np.ndarray, bit_width: int, peak_weight_bits: int,
                  peak_input_bits: int, fdt) -> Tuple[np.ndarray, np.ndarray]:
    """Eqs. (1)-(13) for config columns `c` ([C, 1]) against op rows `o`
    ([1, U]): (latency cycles [C], valid [C])."""
    # effective tiling (T* clamped into [1, N*]; Tkx = Nkx, Tky = Nky)
    tif = np.minimum(c["tif"], o["nif"])
    tix = np.minimum(c["tix"], o["nix"])
    tiy = np.minimum(c["tiy"], o["niy"])
    tof = np.minimum(c["tof"], o["nof"])
    tkx, tky = o["nkx"], o["nky"]
    tox = np.clip((tix - o["nkx"]) // o["s"] + 1, 1, o["nox"])
    toy = np.clip((tiy - o["nky"]) // o["s"] + 1, 1, o["noy"])

    # effective unrolling (P* <= T* <= N*)
    pif = np.minimum(c["pif"], tif)
    pof = np.minimum(c["pof"], tof)
    pox = np.minimum(c["pox"], tox)
    poy = np.minimum(c["poy"], toy)
    pkx = np.minimum(c["pkx"], tkx)
    pky = np.minimum(c["pky"], tky)
    pb = np.minimum(c["pb"], o["batch"])

    # Eq. (9): enough MACs for the unrolled loop body
    unroll = pif * pof * pox * poy * pkx * pky * pb
    valid_macs = unroll <= c["pe_group"] * c["mac_per_group"]

    # Eqs. (3)-(4): inter-tiling x inner-tiling compute cycles
    inter = (_ceil_div(o["nif"], tif) * _ceil_div(o["nkx"], tkx)
             * _ceil_div(o["nky"], tky) * _ceil_div(o["nox"], tox)
             * _ceil_div(o["noy"], toy) * _ceil_div(o["nof"], tof))
    inner = (_ceil_div(tif, pif) * _ceil_div(tkx, pkx) * _ceil_div(tky, pky)
             * _ceil_div(tox, pox) * _ceil_div(toy, poy)
             * _ceil_div(tof, pof))
    compute = inter * inner * _ceil_div(o["batch"], pb) * o["repeat"]

    # Eqs. (1)-(2): weight and input reuse
    weight_reuse = pox * poy * pb
    in_win = ((pox - 1) * o["s"] + pkx) * ((poy - 1) * o["s"] + pky)
    input_reuse = np.maximum((pof * pkx * pky * pox * poy)
                             // np.maximum(in_win, 1), 1)

    # Eqs. (5)-(6) fetch volumes, and the stationary dataflows' refetches
    weight_elems = o["nif"] * o["nkx"] * o["nky"] * o["nof"] * o["repeat"]
    input_elems = o["nif"] * o["nix"] * o["niy"] * o["repeat"]
    num_weight = (o["nox"] * o["noy"] * o["nkx"] * o["nky"] * o["nif"]
                  * o["nof"] * o["repeat"]).astype(fdt)
    num_input = num_weight * o["batch"].astype(fdt)
    spatial_tiles = _ceil_div(o["nox"], tox) * _ceil_div(o["noy"], toy)
    ofm_tiles = _ceil_div(o["nof"], tof)
    ws_weight = weight_elems.astype(fdt)
    stream_input = (input_elems * o["batch"] * ofm_tiles).astype(fdt)
    refetch_weight = (weight_elems * spatial_tiles).astype(fdt)
    is_input = (input_elems * o["batch"]).astype(fdt)

    lo = c["loop_order"]
    num_weight_eff = np.where(
        lo == PAPER, num_weight / np.maximum(weight_reuse, 1).astype(fdt),
        np.where(lo == WEIGHT_STATIONARY, ws_weight, refetch_weight))
    num_input_eff = np.where(
        lo == PAPER, num_input / np.maximum(input_reuse, 1).astype(fdt),
        np.where(lo == INPUT_STATIONARY, is_input, stream_input))

    # Eqs. (7)-(8): fetch cycles at the banks' word bandwidth
    wbw = np.maximum(c["weight_banks_pg"] * c["pe_group"] * c["bank_width"]
                     // bit_width, 1).astype(fdt)
    abw = np.maximum(c["act_banks_pg"] * c["pe_group"] * c["bank_width"]
                     // bit_width, 1).astype(fdt)
    weight_cycles = np.ceil(num_weight_eff / wbw)
    input_cycles = np.ceil(num_input_eff / abw)
    total = np.maximum(compute.astype(fdt),
                       np.maximum(weight_cycles, input_cycles))

    # Eqs. (10)-(13): buffer capacities
    wbuf = (c["weight_banks_pg"] * c["pe_group"] * c["bank_height"]
            * c["bank_width"])
    abuf = (c["act_banks_pg"] * c["pe_group"] * c["bank_height"]
            * c["bank_width"])
    need_w_tile = tkx * tky * tif * tof * bit_width
    need_a_tile = (tix * tiy * tif + tox * toy * tof) * bit_width
    valid = valid_macs & (wbuf >= need_w_tile) & (abuf >= need_a_tile)
    valid = valid.all(axis=1)
    if peak_weight_bits:
        valid &= (wbuf >= peak_weight_bits)[:, 0]
    if peak_input_bits:
        valid &= (abuf >= peak_input_bits * int(o["batch"].max()))[:, 0]

    cycles = (total * counts[None, :].astype(fdt)).sum(axis=1, dtype=fdt)
    return cycles, valid


def gops(configs: Dict[str, np.ndarray], ops: Dict[str, np.ndarray],
         hw: Dict[str, float], peak_weight_bits: int = 0,
         peak_input_bits: int = 0, float_dtype=np.float64) -> np.ndarray:
    """[N] GOPS of each configuration on one op stream; 0 where any of
    Eqs. (9)-(13) is violated (the paper plots such points at 0, Fig. 7)."""
    fdt = np.dtype(float_dtype).type
    cols = {f: np.asarray(configs[f], dtype=np.int64).ravel()
            for f in CONFIG_FIELDS}
    n = len(cols["pe_group"])
    o, counts = unique_ops(ops)
    ops_total = fdt(total_ops(ops))
    freq = fdt(hw["frequency_hz"])
    out = np.zeros(n, dtype=fdt)
    for lo in range(0, n, ROW_CHUNK):
        c = {f: v[lo:lo + ROW_CHUNK, None] for f, v in cols.items()}
        cycles, valid = _cycles_valid(c, o, counts, int(hw["bit_width"]),
                                      int(peak_weight_bits),
                                      int(peak_input_bits), fdt)
        seconds = cycles / freq
        ok = valid & (cycles > 0)
        out[lo:lo + ROW_CHUNK] = np.where(
            ok, ops_total / np.maximum(seconds, fdt(1e-30)) / fdt(1e9),
            fdt(0))
    return out.astype(np.float64)


def area(configs: Dict[str, np.ndarray], hw: Dict[str, float],
         float_dtype=np.float64) -> np.ndarray:
    """[N] §4.3 unit area: MACs with their register files, SRAM bits,
    and one controller per PE group."""
    fdt = np.dtype(float_dtype).type
    c = {f: np.asarray(configs[f], dtype=np.int64).ravel()
         for f in CONFIG_FIELDS}
    total_macs = c["pe_group"] * c["mac_per_group"]
    sram_bits = ((c["weight_banks_pg"] + c["act_banks_pg"]) * c["pe_group"]
                 * c["bank_height"] * c["bank_width"])
    if fdt is np.float64:
        out = (total_macs * (hw["area_per_mac"] + hw["area_per_mac_regfile"])
               + sram_bits * hw["area_per_sram_bit"]
               + c["pe_group"] * hw["area_per_group_ctrl"])
    else:
        out = (total_macs.astype(fdt) * (fdt(hw["area_per_mac"])
                                         + fdt(hw["area_per_mac_regfile"]))
               + sram_bits.astype(fdt) * fdt(hw["area_per_sram_bit"])
               + c["pe_group"].astype(fdt) * fdt(hw["area_per_group_ctrl"]))
    return np.asarray(out, dtype=np.float64)


def geomean(x: np.ndarray, axis: int = 0) -> np.ndarray:
    """Geometric mean with a 1e-12 floor (a zero-GOPS app does not make
    the logarithm undefined)."""
    x = np.maximum(np.asarray(x, dtype=np.float64), 1e-12)
    return np.exp(np.log(x).mean(axis=axis))
