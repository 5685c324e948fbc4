"""The benchmark's own tests: on the CPU, at tiny sizes.

A package, so that its modules import as ``perfbench.tests.*`` and share
no module name with the program's ``tests/``."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
