"""The benchmark's tests run from the checkout root:

    python -m pytest chipbench/tests

They need no chip: JAX is held to the CPU, and the runners are driven
through their functions at tiny sizes."""
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
