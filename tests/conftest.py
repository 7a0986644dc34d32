import os
import sys

# NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see the
# single real device; a test that needs several host devices sets the flag
# in a subprocess of its own.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# repo root, so `from benchmarks import ...` works regardless of invocation
sys.path.insert(1, os.path.join(os.path.dirname(__file__), ".."))
