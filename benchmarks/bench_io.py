"""Shared result I/O for the BENCH_*.json files.

The committed benchmark baselines (BENCH_study.json,
BENCH_surrogate.json, BENCH_composition.json) used to be written by
hand-rolled `json.dumps` calls with nothing but the raw numbers; a
regression investigated weeks later had no record of which host, commit,
or date produced the baseline.  Every writer now goes through
`write_results`, which wraps the benchmark's flat payload in one shared
envelope::

    {
      "bench_schema": 2,
      "bench": "study_scaling",
      "host": {"platform": ..., "python": ..., "cpu_count": ...},
      "git_rev": "f1c3693",            # null outside a git checkout
      "timestamp": "2026-08-08T12:34:56Z",
      "results": { ...the benchmark's own numbers, unchanged... }
    }

`read_results` returns the flat payload from either format (legacy files
have no ``bench_schema`` key), so `--check` gates keep working against
baselines produced before the envelope existed.
"""

from __future__ import annotations

import datetime
import json
import os
import platform
import subprocess
import sys
from pathlib import Path
from typing import Any, Dict, Optional

__all__ = ["BENCH_SCHEMA", "host_info", "git_rev", "write_results",
           "read_results", "read_envelope"]

BENCH_SCHEMA = 2

_ROOT = Path(__file__).resolve().parents[1]


def host_info() -> Dict[str, Any]:
    return {
        "platform": platform.platform(),
        "machine": platform.machine(),
        "python": sys.version.split()[0],
        "cpu_count": os.cpu_count(),
    }


def git_rev(root: Path = _ROOT) -> Optional[str]:
    """Short HEAD revision, or None outside a git checkout / without git."""
    try:
        out = subprocess.run(
            ["git", "-C", str(root), "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10)
    except (OSError, subprocess.SubprocessError):
        return None
    rev = out.stdout.strip()
    return rev if out.returncode == 0 and rev else None


def write_results(path, bench: str, results: Dict[str, Any]) -> Path:
    """Wrap `results` in the shared envelope and write it to `path`."""
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    rec = {
        "bench_schema": BENCH_SCHEMA,
        "bench": bench,
        "host": host_info(),
        "git_rev": git_rev(),
        "timestamp": datetime.datetime.now(datetime.timezone.utc)
        .strftime("%Y-%m-%dT%H:%M:%SZ"),
        "results": results,
    }
    path.write_text(json.dumps(rec, indent=2) + "\n")
    return path


def read_envelope(path) -> Dict[str, Any]:
    """The full record: legacy flat files are wrapped on the fly (host /
    git_rev / timestamp None, `bench` from the filename)."""
    path = Path(path)
    rec = json.loads(path.read_text())
    if isinstance(rec, dict) and "bench_schema" in rec:
        return rec
    return {"bench_schema": 1, "bench": path.stem, "host": None,
            "git_rev": None, "timestamp": None, "results": rec}


def read_results(path) -> Dict[str, Any]:
    """The benchmark's flat payload, from either schema generation."""
    return read_envelope(path)["results"]
