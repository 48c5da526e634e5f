"""Test set-up for the benchmark's own CPU tests: its modules and the
program are importable, and no test here describes or touches a TPU."""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
for p in (HERE, HERE.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
