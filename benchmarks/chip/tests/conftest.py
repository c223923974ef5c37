"""The benchmark's own tests run on the CPU: `python -m pytest
benchmarks/chip/tests` from the repository root."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
