"""Tests of the chip benchmark's harness, run on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
sys.path[:0] = [str(CHIP), str(CHIP.parent.parent / "src")]
