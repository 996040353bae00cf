import os
import sys

# tests run on the CPU, Pallas kernels in interpret mode
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# tier-1 runs with the static verifier on: every compile_query/plan_skim
# in the suite proves its artifact's invariants (repro.analysis.verify).
# Benchmarks force it off — verification is a test-time gate, not a cost.
os.environ.setdefault("REPRO_VERIFY", "1")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
