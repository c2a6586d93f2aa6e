"""The benchmark's own tests run on the CPU at tiny sizes: hold JAX to
it before anything imports jax (``pytest benchmark/tests``)."""

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
