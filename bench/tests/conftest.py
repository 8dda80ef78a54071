"""The benchmark's own tests run on the CPU at smoke sizes:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They put the checkout and its src/ on the path the way bench/run.py does."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
