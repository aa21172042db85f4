"""Shared set-up of the benchmark's own tests (run them with
``python -m pytest bench/tests``; the repository's pytest.ini collects
only tests/)."""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
