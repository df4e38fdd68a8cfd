"""The benchmark's own tests run on the CPU at tiny sizes: the TPU-shaped
kernels, Pallas interpreted. Nothing here measures a speed, and no run
here is a result."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("PINOT_CPU_FAST_GROUPBY", "0")
os.environ.setdefault("PINOT_PALLAS_INTERPRET", "1")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
