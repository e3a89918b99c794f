"""Bytes one scorer call has to move, and the chip's peaks.

A scorer call takes `n` configurations of the design space and one op
stream, and returns one GOPS figure per configuration.  Whatever
implements it has to read every configuration, read the op stream and
write the answers, so from shapes alone:

- one byte per design variable of each configuration (every Table 2
  domain has fewer than 256 values, so a domain index fits a byte);
- four bytes per entry of the op table, `OP_FIELDS` loop bounds for each
  of the stream's ops;
- four bytes per GOPS figure written back.

The v5e publishes no peak for the 64-bit integer and float vector
arithmetic the cost model does, so the only roofline is HBM bandwidth.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"

OP_TABLE_ROWS = 11      # loop bounds + stride, batch and repeat per op


def call_bytes(n_configs: int, n_variables: int, n_ops: int) -> int:
    """Least bytes an exact scorer call moves for `n_configs` configs of
    `n_variables` design variables against a stream of `n_ops` ops."""
    return (int(n_configs) * int(n_variables)
            + 4 * OP_TABLE_ROWS * int(n_ops)
            + 4 * int(n_configs))


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip; a device not in the table is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def hbm_roofline_pct(total_bytes: float, kernel_s: float,
                     device_kind: str) -> float:
    """Share, in %, of the HBM-bound least time that the kernel took."""
    least_s = total_bytes / peaks(device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
