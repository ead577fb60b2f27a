import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
for p in (str(HERE), str(BENCH), str(BENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
