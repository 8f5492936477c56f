"""The benchmark's own tests, collected with the rest of ``tests/``; alone
with ``python -m pytest tests/bench`` from the root of the checkout, on the
CPU (``JAX_PLATFORMS=cpu``)."""
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
for p in (HERE, HERE.parents[1] / "bench", HERE.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
