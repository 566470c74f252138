"""The benchmark's own tests, on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest -q benchmarks/chip/tests

They import the benchmark's code and the program from the checkout."""

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
for p in (str(HERE), str(HERE.parents[1] / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
