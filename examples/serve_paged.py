"""Paged-KV serving walk-through: continuous batching over a fragmented
block pool, then a Nezha-style cache GC (the kv_compaction kernel) restoring
contiguous layout — outputs are bit-identical before/after.

  PYTHONPATH=src python examples/serve_paged.py                 # on a TPU
  PYTHONPATH=src python examples/serve_paged.py --kv-backend pallas_interpret
"""
import argparse

import jax
import numpy as np

from repro.configs import get
from repro.kernels.kv_compaction.ops import BACKENDS
from repro.models import init_params
from repro.serve.engine import ServingEngine

ap = argparse.ArgumentParser()
ap.add_argument("--kv-backend", default="pallas", choices=BACKENDS)
backend = ap.parse_args().kv_backend

cfg = get("smollm_135m", smoke=True).replace(param_dtype="float32",
                                             kv_block_size=8)
params = init_params(jax.random.PRNGKey(0), cfg)
eng = ServingEngine(cfg, params, max_slots=3, max_seq=64,
                    scramble_blocks=True)

rng = np.random.default_rng(0)
print("== submitting 7 requests into 3 slots (continuous batching) ==")
for i in range(7):
    prompt = rng.integers(0, cfg.vocab_size, int(rng.integers(2, 6))).tolist()
    eng.submit(prompt, max_new=6)
tok = eng.run_until_drained()
print(f"   {tok} tokens across {eng.decode_steps} lockstep decode steps")
print(f"   block-table fragmentation: {eng.fragmentation():.2f} "
      f"(scattered ValueLog state)")

print(f"== Nezha cache GC (kv_compaction kernel, backend {backend}) ==")
eng.compact(backend=backend)
print(f"   fragmentation after GC: {eng.fragmentation():.2f} "
      f"(sorted ValueLog state)")

r = eng.submit([5, 4, 3, 2], max_new=6)
eng.run_until_drained()
print(f"   post-GC decode still correct: req{r.rid} -> {r.out}")
print("OK")
