"""The benchmark's own tests: ``python -m pytest benchmarks/chip/tests``
from the root of a checkout, on the CPU (``JAX_PLATFORMS=cpu``)."""
import pathlib
import sys

CHIP = pathlib.Path(__file__).resolve().parents[1]
for p in (str(CHIP), str(CHIP.parents[1] / "src"), str(CHIP / "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)
