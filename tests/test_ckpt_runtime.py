"""Checkpoint store + fault-tolerant runtime integration tests."""
import os
import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.nezha_store import NezhaCheckpointStore
from repro.configs import get, ShapeConfig
from repro.core.metrics import Metrics
from repro.data.pipeline import TokenPipeline
from repro.launch.mesh import make_host_mesh
from repro.runtime.coordinator import Coordinator, TrainRunner

SHAPE = ShapeConfig("t", seq_len=32, global_batch=4, kind="train")


def tiny_tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"a": jax.random.normal(k, (64, 32)),
            "b": {"w": jax.random.normal(k, (8,)),
                  "s": jnp.zeros((), jnp.int32)}}


def test_ckpt_roundtrip_and_single_write():
    wd = tempfile.mkdtemp()
    m = Metrics()
    store = NezhaCheckpointStore(wd, m, gc_threshold_bytes=1 << 60)
    tree = tiny_tree()
    store.save(10, tree)
    user = sum(np.asarray(l).nbytes for l in jax.tree.leaves(tree))
    assert m.write_bytes["ckpt_valuelog"] == user          # exactly once
    restored, step = store.restore(tree)
    assert step == 10
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    store.close()


def test_ckpt_gc_compacts_and_keeps_latest():
    wd = tempfile.mkdtemp()
    store = NezhaCheckpointStore(wd, gc_threshold_bytes=1 << 60, keep=2)
    for s in range(1, 6):
        store.save(s, tiny_tree(seed=s))
    store.gc()
    assert sorted(store.manifests) == [4, 5]
    r4, _ = store.restore(tiny_tree(), step=4)
    exp = tiny_tree(seed=4)
    for a, b in zip(jax.tree.leaves(exp), jax.tree.leaves(r4)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # old vlogs physically removed
    vlogs = [f for f in os.listdir(wd) if f.endswith(".vlog")]
    assert len(vlogs) == 1
    store.close()


def test_ckpt_counters_tell_save_appends_from_gc_rewrites():
    """A save's appends count as ckpt_valuelog and GC's rewrites as ckpt_gc,
    whichever value log (the first, or one a GC made) they go to."""
    wd = tempfile.mkdtemp()
    m = Metrics()
    store = NezhaCheckpointStore(wd, m, gc_threshold_bytes=1, keep=2)
    size = sum(np.asarray(l).nbytes for l in jax.tree.leaves(tiny_tree()))
    store.save(1, tiny_tree(seed=1))   # GC 1 rewrites checkpoint 1
    store.save(2, tiny_tree(seed=2))   # into GC 1's log; GC 2 rewrites 1, 2
    assert store.gen == 2
    assert m.write_bytes["ckpt_valuelog"] == 2 * size
    assert m.write_bytes["ckpt_gc"] == 3 * size
    for step in (1, 2):             # each rewritten whole, tail included
        restored, _ = store.restore(tiny_tree(), step=step)
        for a, b in zip(jax.tree.leaves(tiny_tree(seed=step)),
                        jax.tree.leaves(restored)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    store.close()


def test_ckpt_reload_from_disk():
    wd = tempfile.mkdtemp()
    store = NezhaCheckpointStore(wd)
    store.save(3, tiny_tree(seed=3))
    store.close()
    store2 = NezhaCheckpointStore(wd)
    assert store2.latest_step() == 3
    r, _ = store2.restore(tiny_tree())
    exp = tiny_tree(seed=3)
    assert np.array_equal(np.asarray(r["a"]), np.asarray(exp["a"]))
    store2.close()


@pytest.mark.slow
def test_crash_restore_bit_identical_losses():
    cfg = get("smollm_135m", smoke=True)
    mesh = make_host_mesh()
    wd1, wd2 = tempfile.mkdtemp(), tempfile.mkdtemp()
    try:
        r = TrainRunner(cfg, SHAPE, mesh, wd1, seed=7, ckpt_every=4)
        r.init_or_restore()
        ref = r.run(12)

        r2 = TrainRunner(cfg, SHAPE, mesh, wd2, seed=7, ckpt_every=4)
        r2.init_or_restore()
        with pytest.raises(RuntimeError):
            r2.run(12, crash_at=10)
        r3 = TrainRunner(cfg, SHAPE, mesh, wd2, seed=7, ckpt_every=4)
        start = r3.init_or_restore()
        assert start == 8
        resumed = r3.run(12)
        assert ref[start:] == resumed
    finally:
        shutil.rmtree(wd1, ignore_errors=True)
        shutil.rmtree(wd2, ignore_errors=True)


def test_straggler_detection():
    wd = tempfile.mkdtemp()
    coord = Coordinator(wd, n_controllers=3)
    try:
        t = 100.0
        for step in range(8):
            for h in (0, 1):
                coord.heartbeat(h, step, t)
            t += 1.0
        coord.heartbeat(0, 8, t)          # host 1 goes quiet
        coord.heartbeat(0, 9, t + 1)
        coord.heartbeat(0, 10, t + 2)
        lag = coord.stragglers(now=t + 3.5, hosts=[0, 1])
        assert lag == [1]                 # host0 lag 1.5 < 3x median(1.0)
    finally:
        coord.destroy()


def test_elastic_restore_to_new_mesh():
    """Manifest is mesh-agnostic: save under one mesh, restore under another
    sharding layout (elastic rescale path)."""
    cfg = get("smollm_135m", smoke=True)
    mesh = make_host_mesh()
    wd = tempfile.mkdtemp()
    try:
        from repro.launch import steps as S
        init_fn, st_sh = S.make_init_fn(cfg, mesh)
        state = init_fn(jax.random.PRNGKey(0))
        store = NezhaCheckpointStore(f"{wd}/ck")
        store.save(1, jax.tree.map(np.asarray, state))
        # "rescale": restore into a fresh mesh (same devices here, but the
        # path exercises manifest-driven resharding end-to-end)
        mesh2 = make_host_mesh()
        init2, st_sh2 = S.make_init_fn(cfg, mesh2)
        tmpl = S.abstract_state(cfg)
        host_tree, step = store.restore(tmpl)
        resharded = jax.tree.map(lambda a, sh: jax.device_put(a, sh),
                                 host_tree, st_sh2)
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(resharded)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        store.close()
    finally:
        shutil.rmtree(wd, ignore_errors=True)


def test_pipeline_restart_determinism():
    cfg = get("smollm_135m", smoke=True)
    p1 = TokenPipeline(cfg, SHAPE, seed=3, start_step=0)
    b5 = p1.batch_for_step(5)
    p1.close()
    p2 = TokenPipeline(cfg, SHAPE, seed=3, start_step=5)
    b5b = p2.batch_for_step(5)
    p2.close()
    assert np.array_equal(b5["tokens"], b5b["tokens"])
    assert np.array_equal(b5["labels"], b5b["labels"])
