"""Puts the benchmark's directory and the program on the import path for
its tests (the harness imports its own modules by name)."""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
for p in (str(HERE.parents[1] / "src"), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)
