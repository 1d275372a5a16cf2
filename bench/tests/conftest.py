"""The benchmark's modules import each other by name from ``bench/``; the
program comes from ``src/``."""
import sys
from pathlib import Path

_BENCH = Path(__file__).resolve().parents[1]
for p in (_BENCH, _BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
