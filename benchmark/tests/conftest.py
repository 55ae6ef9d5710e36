"""CPU tests of the benchmark: JAX on the CPU, before it is imported."""

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
