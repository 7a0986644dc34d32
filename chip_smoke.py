"""Run the store and its two device paths once on a TPU, at full width.

  python chip_smoke.py               # one chip: store, train-checkpoint, serve
  python chip_smoke.py --four-chips  # save on a 2x2 mesh, resume on a 4x1 one

Default mode runs three phases, each pinned to jax.devices()[0]:

  store  a 3-replica synced nezha Cluster with its default group commit:
         100,000 YCSB core records of 1,000 B (10 fields x 100 B, >= 10x
         the block cache), then 5,000 open-loop YCSB-B operations (95%
         reads, 5% updates, Zipfian keys, 1,000/s); the whole history is
         checked for linearizability.
  train  full-width smollm_135m through TrainRunner + Coordinator: batch
         8 x 512 tokens, a checkpoint every 3 steps, a crash after step 4,
         resume from step 3.  The restored state must be byte-equal to the
         uninterrupted run's step-3 state and the resumed losses equal to
         its losses, bit for bit.
  serve  full-width smollm_135m ServingEngine (32 slots x 2,048 tokens of
         paged KV): 64 seeded requests, one arriving per engine step, and
         the compiled kv_compaction kernel after every 8 finished
         requests.  Each compaction must be byte-equal to the jnp
         reference, and the tokens equal to a run without compaction.

--four-chips runs only the elastic-resume path: 3 steps on a (data=2,
model=2) mesh, a checkpoint through the store, a restore onto (data=4,
model=1) and 3 more steps, against the same 6 steps on one chip.

Exits non-zero, printing no result, unless JAX's first device is a TPU.
Every failed check raises.  Each phase prints its results, seconds and
backend compile seconds; the last line of stdout is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import ShapeConfig, get  # noqa: E402
from repro.core.cluster import Cluster  # noqa: E402
from repro.core.workload import Tenant, WorkloadSpec, run_workload  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.runtime.coordinator import Coordinator, TrainRunner  # noqa: E402
from repro.serve.engine import ServingEngine, compact_caches  # noqa: E402
from repro.utils import enable_compile_cache  # noqa: E402

ARCH = "smollm_135m"
STORE = dict(n_keys=100_000, n_ops=5_000, vsize=1_000)
TRAIN_SHAPE = ShapeConfig("chip_smoke", seq_len=512, global_batch=8,
                          kind="train")
SERVE = dict(slots=32, max_seq=2048, n_requests=64, prompt_len=(128, 1024),
             max_new=32, compact_every=8)
# losses of one program sharded two ways differ by reduction order only;
# bf16 weights make that a few bf16 ulps (2^-8 each) of the loss
LOSS_RTOL = 1e-2


class SmokeFailure(AssertionError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Backend compile seconds and persistent-cache hits inside a `with`."""

    def __enter__(self):
        self.seconds, self.cache_hits = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


@jax.jit
def _same_bits(x, y):
    if jnp.issubdtype(x.dtype, jnp.floating):
        bits = jnp.dtype(f"uint{8 * x.dtype.itemsize}")
        x = jax.lax.bitcast_convert_type(x, bits)
        y = jax.lax.bitcast_convert_type(y, bits)
    return jnp.array_equal(x, y)


def bytes_equal(a, b) -> bool:
    """Same tree, dtypes, shapes and bytes (NaN payloads included), compared
    on the device: a pool in HBM is never copied to the host for it."""
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    return ta == tb and all(
        x.dtype == y.dtype and x.shape == y.shape and bool(_same_bits(x, y))
        for x, y in zip(la, lb))


# ------------------------------------------------------------------ phases
def store_phase(workdir: str, *, n_keys: int, n_ops: int, vsize: int,
                seed: int) -> dict:
    cluster = Cluster(n=3, engine="nezha", workdir=f"{workdir}/store",
                      seed=seed, sync=True)
    try:
        cluster.elect()
        spec = WorkloadSpec(rate=1000.0, n_ops=n_ops, n_keys=n_keys,
                            vsize=vsize, seed=seed,
                            tenants=(Tenant(mix="B"),))
        report = run_workload(cluster, spec, check=True)
        cache_bytes = cluster.engines[0].cache.capacity
        fsyncs = sum(m.fsyncs for m in cluster.metrics)
    finally:
        cluster.destroy()
    ops = sum(report.phase_ops.values())
    check(ops == n_ops, f"store ran {ops} of {n_ops} operations")
    check(not report.violations,
          f"{len(report.violations)} history violations, first: "
          f"{report.violations[:3]}")
    return {"keys": n_keys, "bytes_loaded": n_keys * vsize,
            "cache_bytes": cache_bytes,
            "data_over_cache": n_keys * vsize / cache_bytes,
            "ops": ops, "violations": len(report.violations),
            "fsyncs": fsyncs}


def _ckpt_bytes(store, step: int) -> int:
    return sum(e["length"] for e in store.manifests[step]["entries"].values())


def train_phase(cfg, shape, device, workdir: str, *, seed: int,
                steps: int = 6, ckpt_every: int = 3,
                crash_at: int = 4) -> dict:
    mesh = make_host_mesh(devices=[device])

    def runner(wd, coord):
        return TrainRunner(cfg, shape, mesh, wd, seed=seed,
                           ckpt_every=ckpt_every, coordinator=coord)

    # uninterrupted reference; its live state at the first checkpoint is
    # exactly what that save wrote
    coord = Coordinator(f"{workdir}/ref", seed=seed)
    try:
        ref = runner(f"{workdir}/ref", coord)
        ref.init_or_restore()
        losses = ref.run(ckpt_every)
        saved = jax.tree.map(np.asarray, ref.state)
        losses += ref.run(steps)
        save_s = list(ref.save_seconds)
        ckpt_bytes = _ckpt_bytes(ref.store, ckpt_every)
        ref.store.close()
        del ref
    finally:
        coord.destroy()
    shutil.rmtree(f"{workdir}/ref")

    coord = Coordinator(f"{workdir}/crash", seed=seed)
    try:
        crashed = runner(f"{workdir}/crash", coord)
        crashed.init_or_restore()
        try:
            crashed.run(steps, crash_at=crash_at)
        except RuntimeError as e:         # the injected failure, only it
            if str(e) != f"injected host failure at {crash_at}":
                raise
        else:
            raise SmokeFailure(f"no failure was injected at {crash_at}")
        save_s += crashed.save_seconds
        crashed.store.close()
        del crashed
        # a fresh host resumes from the control plane's last checkpoint
        resumed = runner(f"{workdir}/crash", coord)
        start = resumed.init_or_restore()
        check(start == ckpt_every, f"resumed at step {start}, expected the "
              f"step-{ckpt_every} checkpoint")
        check(bytes_equal(resumed.state, saved),
              f"restored state differs from the step-{start} state")
        del saved
        resumed_losses = resumed.run(steps)
        check(resumed_losses == losses[start:],
              f"resumed losses {resumed_losses} != uninterrupted "
              f"{losses[start:]}")
        save_s += resumed.save_seconds
        committed = coord.committed_steps("ckpt")
        resumed.store.close()
    finally:
        coord.destroy()
    check(committed == list(range(ckpt_every, steps + 1, ckpt_every)),
          f"control plane committed checkpoints {committed}")
    return {"steps": steps, "crash_at": crash_at, "resumed_from": start,
            "losses": losses, "resumed_losses": resumed_losses,
            "resumed_bit_identical": True, "restore_byte_equal": True,
            "committed_ckpts": committed, "ckpt_bytes": ckpt_bytes,
            "save_s": save_s, "restore_s": resumed.restore_seconds}


def _serve(cfg, prompts, *, seed, slots, max_seq, max_new, compact_every,
           backend):
    """One engine over `prompts`, one arrival per engine step; with a
    `backend`, compacts after every `compact_every` finished requests."""
    eng = ServingEngine(cfg, max_slots=slots, max_seq=max_seq, seed=seed)
    pending = list(prompts)
    compactions = []
    t0 = time.perf_counter()
    while pending or eng.active or eng.queue:
        if pending:
            eng.submit(pending.pop(0), max_new=max_new)
        done = len(eng.finished)
        eng.step()
        if backend and \
                len(eng.finished) // compact_every > done // compact_every:
            before, frag = eng.caches, float(eng.fragmentation())
            eng.compact(backend=backend)
            same = bytes_equal(eng.caches,
                               compact_caches(before, "reference"))
            check(same, f"compaction {len(compactions) + 1} ({backend}) "
                  "differs from compact_kv_pool_ref")
            del before
            compactions.append({"finished": len(eng.finished),
                                "active": len(eng.active),
                                "frag_before": frag,
                                "frag_after": float(eng.fragmentation())})
    seconds = time.perf_counter() - t0
    outputs = {r.rid: r.out for r in eng.finished}
    return {"outputs": outputs, "seconds": seconds,
            "tokens": sum(len(o) for o in outputs.values()),
            "decode_steps": eng.decode_steps, "compactions": compactions}


def serve_phase(cfg, device, *, backend: str, seed: int, slots: int,
                max_seq: int, n_requests: int, prompt_len, max_new: int,
                compact_every: int) -> dict:
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size,
                            int(rng.integers(prompt_len[0], prompt_len[1]
                                             + 1))).tolist()
               for _ in range(n_requests)]
    kw = dict(seed=seed, slots=slots, max_seq=max_seq, max_new=max_new,
              compact_every=compact_every)
    with jax.default_device(device):
        gc_run = _serve(cfg, prompts, backend=backend, **kw)
        plain = _serve(cfg, prompts, backend=None, **kw)
    check(len(gc_run["outputs"]) == n_requests,
          f"{len(gc_run['outputs'])} of {n_requests} requests finished")
    check(gc_run["outputs"] == plain["outputs"],
          "tokens with compaction differ from the run without it")
    check(len(gc_run["compactions"]) == n_requests // compact_every,
          f"{len(gc_run['compactions'])} compactions")
    stats = device.memory_stats() or {}
    return {"backend": backend, "requests": n_requests,
            "tokens": gc_run["tokens"], "decode_steps": gc_run["decode_steps"],
            "compactions": gc_run["compactions"],
            "compactions_byte_equal_ref": True,
            "tokens_equal_no_compaction": True,
            "serve_s": gc_run["seconds"], "serve_no_gc_s": plain["seconds"],
            "peak_bytes_in_use": stats.get("peak_bytes_in_use",
                                           "not reported")}


def four_chip_phase(cfg, shape, devices, workdir: str, *, seed: int,
                    steps: int = 6) -> dict:
    half = steps // 2
    mesh_a = make_host_mesh(model=2, devices=devices)     # (data=2, model=2)
    mesh_b = make_host_mesh(model=1, devices=devices)     # (data=4, model=1)
    coord = Coordinator(f"{workdir}/elastic", seed=seed)
    try:
        a = TrainRunner(cfg, shape, mesh_a, f"{workdir}/elastic", seed=seed,
                        ckpt_every=half, coordinator=coord)
        a.init_or_restore()
        losses = a.run(half)
        saved = jax.tree.map(np.asarray, a.state)
        save_s = a.save_seconds
        a.store.close()
        del a
        b = TrainRunner(cfg, shape, mesh_b, f"{workdir}/elastic", seed=seed,
                        ckpt_every=half, coordinator=coord)
        start = b.init_or_restore()
        check(start == half, f"resumed at step {start}, expected {half}")
        check(all(len(x.sharding.device_set) == len(devices)
                  for x in jax.tree.leaves(b.state)),
              "restored state is not placed on the 4x1 mesh")
        check(bytes_equal(b.state, saved),
              "state restored onto the 4x1 mesh differs from the saved one")
        del saved
        losses += b.run(steps)
        b.store.close()
    finally:
        coord.destroy()
    one = TrainRunner(cfg, shape, make_host_mesh(devices=devices[:1]),
                      f"{workdir}/one_chip", seed=seed, ckpt_every=steps + 1)
    one.init_or_restore()
    ref = one.run(steps)
    one.store.close()
    rel = [abs(x - y) / abs(y) for x, y in zip(losses, ref)]
    check(max(rel) <= LOSS_RTOL, f"2x2->4x1 losses {losses} vs one chip "
          f"{ref}: max relative gap {max(rel)} > {LOSS_RTOL}")
    return {"meshes": "2x2 -> 4x1", "resumed_from": start,
            "restore_byte_equal": True, "losses": losses,
            "one_chip_losses": ref, "max_rel_gap": max(rel),
            "loss_rtol": LOSS_RTOL, "save_s": save_s,
            "restore_s": b.restore_seconds,
            "peak_bytes_in_use": [(d.memory_stats() or {}).get(
                "peak_bytes_in_use", "not reported") for d in devices]}


def run_phase(name: str, fn, *args, **kw) -> dict:
    with CompileClock() as clock:
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        seconds = time.perf_counter() - t0
    print(f"[{name}] ok seconds={seconds} compile_s={clock.seconds} "
          f"cache_hits={clock.cache_hits}", flush=True)
    for k, v in out.items():
        if isinstance(v, list) and v and isinstance(v[0], dict):
            for i, row in enumerate(v):
                print(f"[{name}] {k}[{i}] {json.dumps(row)}", flush=True)
        else:
            print(f"[{name}] {k}={v}", flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 -> 4x1 elastic resume")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX's first device is {dev.platform} "
                         f"({dev.device_kind}), not a TPU; no phase ran")
    n = 4 if args.four_chips else 1
    devices = jax.devices()[:n]
    check(len(devices) == n, f"{n} chips needed, {len(jax.devices())} found")
    print(f"chip_smoke: device_kind={dev.device_kind} platform={dev.platform} "
          f"chips_used={n} of {len(jax.devices())} jax={jax.__version__}",
          flush=True)
    print(f"chip_smoke: compile cache {enable_compile_cache()}", flush=True)
    cfg = get(ARCH)
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        if args.four_chips:
            run_phase("four_chips", four_chip_phase, cfg, TRAIN_SHAPE,
                      devices, workdir, seed=args.seed)
        else:
            store = run_phase("store", store_phase, workdir, seed=args.seed,
                              **STORE)
            check(store["data_over_cache"] >= 10,
                  "store data is under 10x the block cache")
            run_phase("train", train_phase, cfg, TRAIN_SHAPE, dev, workdir,
                      seed=args.seed)
            run_phase("serve", serve_phase, cfg, dev, backend="pallas",
                      seed=args.seed, **SERVE)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
