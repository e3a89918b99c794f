"""Readings that the limits of the comparison are set from.

    python bench/control.py --workload <cell> --program-seeds 1,2,... \
        [--fault-seeds 4,5,6] --control-seeds 7,8,9 [--out readings.json]

In one process on the chip, at the cell's own size: one warm-up study,
then one study per program seed through the timed path (the lower
readings), then, for each fault of `faults.py` in turn, one study per
fault seed with that fault planted in the device scorer, then one study
per control seed with the program's cost model replaced by the plain
reference computed in float32, the precision below the float64 that the
cost model states (the upper readings): the device scorer and the host
cross-evaluation of the synthesis stage both.  Each study is compared as
a benchmark run compares its checked studies (`check.py`).  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time
from pathlib import Path
from typing import Dict, List

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

from bench import check, faults, harness, reference     # noqa: E402


def reference_scorer(float_dtype):
    """A `FusedJaxScorer.metrics` replacement: the plain reference at
    `float_dtype`, over the scorer's own op stream and peaks."""
    from repro.core.costmodel import ConfigBatch

    def metrics(self, matrix):
        cols = {f: matrix[:, j] for j, f in enumerate(ConfigBatch.FIELDS)}
        stream = self.t.stream
        ops = {f: np.asarray(getattr(stream, f)).ravel()
               for f in reference.OP_FIELDS}
        hw = dataclasses.asdict(self.hw)
        return (reference.gops(cols, ops, hw, self.peak_weight_bits,
                               self.peak_input_bits, float_dtype),
                reference.area(cols, hw, float_dtype))
    return metrics


def reference_gops(float_dtype):
    """A `performance_gops` replacement for the synthesis stage."""
    from repro.core.costmodel import ConfigBatch

    def performance_gops(configs, stream, hw, peak_weight_bits=0,
                         peak_input_bits=0, backend="numpy"):
        m = ConfigBatch.from_configs(configs).matrix
        cols = {f: m[:, j] for j, f in enumerate(ConfigBatch.FIELDS)}
        ops = {f: np.asarray(getattr(stream, f)).ravel()
               for f in reference.OP_FIELDS}
        return reference.gops(cols, ops, dataclasses.asdict(hw),
                              peak_weight_bits, peak_input_bits, float_dtype)
    return performance_gops


def install_control(setattr_=setattr, float_dtype=np.float32) -> None:
    """Put the reference at `float_dtype` in the program's place."""
    import repro.dse.parallel
    import repro.dse.study
    from repro.kernels.costmodel import FusedJaxScorer
    setattr_(FusedJaxScorer, "metrics", reference_scorer(float_dtype))
    for module in (repro.dse.study, repro.dse.parallel):
        setattr_(module, "performance_gops", reference_gops(float_dtype))


def readings(cell: harness.Cell, seeds: List[int]) -> List[Dict]:
    out = []
    for seed in seeds:
        t0 = time.perf_counter()
        study = harness.build_study(cell, seed)
        result = study.run()
        values = check.compare(study, result, cell.config, cell.traffic, 0)
        ok, _ = check.judge(values, cell.traffic["limits"])
        out.append({"seed": seed, "correct": ok,
                    "study_s": time.perf_counter() - t0,
                    **{n: v for n, v in values}})
        print(json.dumps(out[-1]), flush=True)
    return out


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="bench/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", type=Path, default=None)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    try:
        harness.find_chips(cell.chips)
    except harness.NoChip as e:
        print(f"[control] {e}", file=sys.stderr)
        return 1
    from repro.dse.cli import configure_compile_cache
    configure_compile_cache()
    seeds, fault_seeds, controls = (
        [int(s) for s in arg.split(",") if s]
        for arg in (args.program_seeds, args.fault_seeds,
                    args.control_seeds))
    harness.build_study(cell, seeds[0] if seeds else 0).run()   # warm-up
    report = {"workload": cell.name, "program": readings(cell, seeds)}
    for name in faults.FAULTS if fault_seeds else ():
        undo = faults.install(name)
        report[name] = readings(cell, fault_seeds)
        undo()
    install_control()
    report["control"] = readings(cell, controls)
    if args.out is not None:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(json.dumps(report, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
