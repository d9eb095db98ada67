import os
import sys
from pathlib import Path

# the benchmark's tests run on the CPU and import the harness the way
# ``bench/run.py`` does, with ``bench/`` on the path
os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
