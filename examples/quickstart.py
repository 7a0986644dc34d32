"""Quickstart — the whole system in one minute (CPU):

  1. train a reduced llama-family model with the Nezha checkpoint store,
  2. crash it, restore from the last committed manifest, finish training,
  3. serve it with the paged-KV engine and run a cache GC.

  PYTHONPATH=src python examples/quickstart.py --kv-backend pallas_interpret

(--kv-backend defaults to the compiled kv_compaction kernel, which needs a
TPU.)
"""
import argparse
import shutil
import tempfile

import jax

from repro.configs import ShapeConfig, get
from repro.kernels.kv_compaction.ops import BACKENDS
from repro.launch.mesh import make_host_mesh
from repro.runtime.coordinator import TrainRunner
from repro.serve.engine import ServingEngine

ap = argparse.ArgumentParser()
ap.add_argument("--kv-backend", default="pallas", choices=BACKENDS)
backend = ap.parse_args().kv_backend

cfg = get("smollm_135m", smoke=True)
shape = ShapeConfig("qs", seq_len=32, global_batch=4, kind="train")
mesh = make_host_mesh()
wd = tempfile.mkdtemp(prefix="quickstart_")

print("== 1. train (with Nezha KV-separated checkpoints) ==")
runner = TrainRunner(cfg, shape, mesh, wd, seed=0, ckpt_every=5)
runner.init_or_restore()
try:
    runner.run(20, crash_at=13)
except RuntimeError as e:
    print(f"   injected failure: {e}")

print("== 2. restore from the last committed manifest ==")
runner2 = TrainRunner(cfg, shape, mesh, wd, seed=0, ckpt_every=5)
start = runner2.init_or_restore()
print(f"   resumed at step {start}")
losses = runner2.run(20)
print(f"   final loss {losses[-1]:.4f}")

print("== 3. serve with the paged KV cache + Nezha cache GC ==")
params = runner2.state["params"]
host_params = jax.tree.map(lambda a: a, params)
eng = ServingEngine(cfg.replace(kv_block_size=8), host_params,
                    max_slots=2, max_seq=64)
for p in ([3, 1, 4], [1, 5, 9, 2], [6, 5, 3]):
    eng.submit(p, max_new=6)
eng.run_until_drained()
print(f"   served {len(eng.finished)} requests; "
      f"fragmentation={eng.fragmentation():.2f}")
eng.compact(backend=backend)
print(f"   after cache GC ({backend}): fragmentation={eng.fragmentation():.2f}")
for r in eng.finished:
    print(f"   req{r.rid}: {r.prompt} -> {r.out}")
shutil.rmtree(wd, ignore_errors=True)
print("OK")
