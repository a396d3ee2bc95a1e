"""These tests rehearse on the CPU, whatever the machine holds."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("DDS_TPU_MIN_BATCH", "0")   # tiny folds reach the pool
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
