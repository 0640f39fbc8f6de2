"""``python -m pytest bench/tests`` from the checkout's root: the benchmark
and the system under test (``src/``) on the path, JAX on the CPU."""
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
