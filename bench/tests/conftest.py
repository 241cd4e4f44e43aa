import os
import sys

# the benchmark's CPU tests: pin the CPU before anything imports jax, and
# make `bench` and the program's `repro` importable from any launch dir
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)
