"""The benchmark's own tests run on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests
"""

from pathlib import Path
import sys

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
