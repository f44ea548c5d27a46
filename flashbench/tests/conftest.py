"""CPU tests of the benchmark's own pieces: run with
``python -m pytest flashbench/tests`` from the checkout's root."""
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parent.parent / "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
