"""The benchmark's own tests run on the CPU, apart from the repository's
tier-1 suite: ``python -m pytest skimbench/tests`` from the root."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [os.path.join(ROOT, "src"), BENCH_DIR]
