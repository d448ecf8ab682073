"""The benchmark's own tests run on the CPU, at tiny sizes, and never report a
device metric. `JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# workers of a test cluster come up on the CPU whatever they were "granted"
os.environ.setdefault("RAY_TPU_WORKER_JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
