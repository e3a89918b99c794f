"""Makes `bench` and `repro` importable when these tests run by path:
`python -m pytest bench/tests` from the checkout root, on the CPU."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
