"""The benchmark's own tests: CPU rehearsals at tiny sizes, kernels in
Pallas interpret mode. Run with ``python -m pytest benchmarks/chip/tests``."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
