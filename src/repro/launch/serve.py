"""Serving launcher: paged-KV continuous batching with Nezha cache GC.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm_135m --smoke \
      --requests 8 --max-new 12 --compact-every 4

The cache GC runs the compiled kv_compaction kernel (a TPU); on a CPU pass
--kv-backend pallas_interpret (the same kernel, interpreted) or reference.
"""
from __future__ import annotations

import argparse
import time


def main():
    from repro.kernels.kv_compaction.ops import BACKENDS

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm_135m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--compact-every", type=int, default=0,
                    help="run cache GC every N finished requests")
    ap.add_argument("--kv-backend", default="pallas", choices=BACKENDS,
                    help="cache GC kernel (see kernels/kv_compaction/ops.py)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import numpy as np

    from repro.configs import get
    from repro.serve.engine import ServingEngine
    from repro.utils import enable_compile_cache

    enable_compile_cache()
    print(f"[serve] device {jax.devices()[0].device_kind}, cache GC backend "
          f"{args.kv_backend}")
    cfg = get(args.arch, smoke=args.smoke)
    eng = ServingEngine(cfg, max_slots=args.slots, max_seq=args.max_seq,
                        seed=args.seed)
    rng = np.random.default_rng(args.seed)
    for i in range(args.requests):
        plen = int(rng.integers(2, 8))
        prompt = rng.integers(0, cfg.vocab_size, plen).tolist()
        eng.submit(prompt, max_new=args.max_new)
    t0 = time.time()
    done = 0
    while eng.active or eng.queue:
        eng.step()
        newly = len(eng.finished) - done
        if newly and args.compact_every and \
                len(eng.finished) % args.compact_every == 0:
            frag = eng.fragmentation()
            eng.compact(backend=args.kv_backend)
            print(f"[serve] cache GC ({args.kv_backend}): fragmentation "
                  f"{frag:.2f} -> {eng.fragmentation():.2f}")
        done = len(eng.finished)
    dt = time.time() - t0
    tokens = sum(len(r.out) for r in eng.finished)
    print(f"[serve] {len(eng.finished)} requests, {tokens} tokens in "
          f"{dt:.1f}s ({tokens / dt:.1f} tok/s), "
          f"{eng.decode_steps} decode steps, {eng.compactions} GCs")
    for r in eng.finished[:4]:
        print(f"  req{r.rid}: prompt={r.prompt} -> {r.out[:8]}...")


if __name__ == "__main__":
    main()
