"""CPU rehearsal of chip_smoke.py: each phase at smoke size, with the
compaction kernel interpreted, and the script's refusal to run off a TPU."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

import chip_smoke
from repro.configs import ShapeConfig, get

CFG = get("smollm_135m", smoke=True)
SHAPE = ShapeConfig("t", seq_len=32, global_batch=4, kind="train")
ROOT = os.path.join(os.path.dirname(__file__), "..")


def test_store_phase(tmp_path):
    out = chip_smoke.store_phase(str(tmp_path), n_keys=400, n_ops=200,
                                 vsize=1000, seed=3)
    assert out["violations"] == 0 and out["ops"] == 200
    assert out["bytes_loaded"] == 400 * 1000
    assert out["fsyncs"] > 0                   # sync=True: real fsyncs


def test_train_phase_crash_resume(tmp_path):
    out = chip_smoke.train_phase(CFG, SHAPE, jax.devices()[0], str(tmp_path),
                                 seed=7)
    assert out["resumed_from"] == 3
    assert out["resumed_losses"] == out["losses"][3:]
    assert out["committed_ckpts"] == [3, 6]
    # saves: uninterrupted 3 and 6, crashed 3, resumed 6
    assert len(out["save_s"]) == 4 and out["restore_s"] > 0
    assert out["ckpt_bytes"] > CFG.param_count() * 2   # params + m + v


def test_serve_phase_compaction(tmp_path):
    out = chip_smoke.serve_phase(CFG.replace(kv_block_size=8),
                                 jax.devices()[0],
                                 backend="pallas_interpret", seed=1, slots=4,
                                 max_seq=64, n_requests=8, prompt_len=(4, 24),
                                 max_new=4, compact_every=2)
    comps = out["compactions"]
    assert [c["finished"] for c in comps] == [2, 4, 6, 8]
    assert all(c["frag_after"] == 0 for c in comps)
    # the first GCs run under load, on tables that admission scrambled
    assert all(c["frag_before"] > 0 and c["active"] > 0 for c in comps[:3])
    assert out["tokens"] == 8 * (4 + 1)


def test_byte_equality_sees_one_flipped_bit():
    a = {"w": np.arange(12, dtype=np.float32).reshape(3, 4),
         "s": np.zeros((), np.int32)}
    b = jax.tree.map(np.copy, a)
    assert chip_smoke.bytes_equal(a, b)
    b["w"].view(np.uint32)[1, 2] ^= 1
    assert not chip_smoke.bytes_equal(a, b)
    nan = np.float32(np.nan)                   # same value, other payload
    c = {"w": np.full((2,), nan), "s": np.zeros((), np.int32)}
    d = jax.tree.map(np.copy, c)
    d["w"].view(np.uint32)[0] ^= 1
    assert not chip_smoke.bytes_equal(c, d)


def test_main_refuses_to_run_off_a_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert "cpu" in str(e.value.code)
    assert "ok" not in capsys.readouterr().out


FOUR_DEVICES = textwrap.dedent("""
    import json, sys, tempfile
    import jax
    import chip_smoke
    from repro.configs import ShapeConfig, get
    shape = ShapeConfig("t", seq_len=32, global_batch=8, kind="train")
    out = chip_smoke.four_chip_phase(get("smollm_135m", smoke=True), shape,
                                     jax.devices()[:4], tempfile.mkdtemp(),
                                     seed=5)
    print(json.dumps({k: out[k] for k in ("resumed_from", "max_rel_gap")}))
""")


def test_four_chip_phase_on_four_cpu_devices():
    """The 2x2 -> 4x1 elastic resume on virtual devices (the device count
    must be fixed before JAX starts, hence the child process)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), ROOT]))
    r = subprocess.run([sys.executable, "-c", FOUR_DEVICES], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["resumed_from"] == 3
    assert out["max_rel_gap"] <= chip_smoke.LOSS_RTOL


def test_compile_cache_dir(monkeypatch):
    from repro import utils
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert utils.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before  # JAX's own
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert utils.enable_compile_cache() == str(utils.COMPILE_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == \
            str(utils.COMPILE_CACHE_DIR)
        assert utils.COMPILE_CACHE_DIR.parent == Path(ROOT).resolve()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
