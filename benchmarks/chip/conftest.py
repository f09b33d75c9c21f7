"""Puts this directory and the program's ``src`` on the path of the
benchmark's own CPU tests (``test_chipbench_*.py``)."""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
for p in (HERE, HERE.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
