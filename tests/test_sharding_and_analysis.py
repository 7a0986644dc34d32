"""Sharding rules + HLO analyzer unit tests, and an end-to-end multi-device
train step run in a subprocess (device count must be set before jax init)."""
import json
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_analysis import HloModule, analyze, type_bytes
from repro.sharding.rules import fit_spec, make_rules, param_spec
from jax.sharding import AxisType, PartitionSpec as P


def test_type_bytes():
    assert type_bytes("bf16[128,128]{1,0}") == 128 * 128 * 2
    assert type_bytes("(s32[], f32[4,2]{1,0})") == 4 + 32
    assert type_bytes("pred[]") == 1
    # replica_groups must NOT parse as a shape
    assert type_bytes("replica_groups=[32,16]<=[512]") == 0


def test_analyzer_counts_loop_trips_exactly():
    def f(w, x):
        def body(c, wi):
            return c @ wi, ()
        y, _ = jax.lax.scan(body, x, w)
        return y

    w = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    c = jax.jit(f).lower(w, x).compile()
    cost = analyze(c.as_text())
    assert cost.flops == 8 * 2 * 128 ** 3


def test_analyzer_nested_scans():
    def f(w, x):
        def outer(c, wi):
            def inner(c2, _):
                return c2 @ wi, ()
            c2, _ = jax.lax.scan(inner, c, None, length=4)
            return c2, ()
        y, _ = jax.lax.scan(outer, x, w)
        return y

    w = jax.ShapeDtypeStruct((3, 64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    c = jax.jit(f).lower(w, x).compile()
    cost = analyze(c.as_text())
    assert cost.flops == 3 * 4 * 2 * 64 ** 3


def _mesh():
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def test_fit_spec_drops_indivisible_axes():
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    assert fit_spec((7,), P("data"), mesh) == P("data")  # 7 % 1 == 0
    # batch=1 cannot shard over a >1 axis — simulated via spec entries
    rules = make_rules(_mesh())
    s = rules.sharding((1, 1), "batch")
    assert s.spec == P(None, None) or s.spec == P("data", None)


def test_param_spec_routing():
    rules = make_rules(_mesh())
    assert param_spec("layers/0/attn/wq", (4, 64, 64), rules)[0] is None
    assert param_spec("embed/embedding", (128, 64), rules) is not None
    # biases/scales stay replicated
    sp = param_spec("layers/0/attn/wq_bias", (4, 64), rules)
    assert all(a is None for a in tuple(sp))


SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"  # 8 host devices, never real TPU
    import jax, jax.numpy as jnp, json
    from repro.configs import get, ShapeConfig
    from jax.sharding import AxisType
    from repro.launch.steps import make_train_step, make_init_fn, input_specs
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    out = {}
    for arch in ["smollm_135m", "olmoe_1b_7b", "zamba2_1p2b"]:
        cfg = get(arch, smoke=True)
        shape = ShapeConfig("s", seq_len=32, global_batch=8, kind="train")
        init_fn, _ = make_init_fn(cfg, mesh)
        state = init_fn(jax.random.PRNGKey(0))
        step, rules, _, b_sh = make_train_step(cfg, mesh, shape)
        ins = input_specs(cfg, shape)
        key = jax.random.PRNGKey(1)
        batch = {}
        for k, v in ins.items():
            if v.dtype == jnp.int32:
                batch[k] = jax.device_put(
                    jax.random.randint(key, v.shape, 0, cfg.vocab_size),
                    b_sh[k])
            else:
                batch[k] = jax.device_put(
                    jax.random.normal(key, v.shape, v.dtype), b_sh[k])
        l0 = None
        for _ in range(3):
            state, metrics = step(state, batch)
            l0 = l0 or float(metrics["loss"])
        out[arch] = [l0, float(metrics["loss"])]
    print("RESULT " + json.dumps(out))
""")


@pytest.mark.slow
def test_multi_device_train_step_subprocess():
    r = subprocess.run([sys.executable, "-c", SUBPROC], capture_output=True,
                       text=True, timeout=900,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root"})
    assert r.returncode == 0, r.stderr[-2000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT")][0]
    res = json.loads(line[len("RESULT "):])
    for arch, (first, last) in res.items():
        assert last < first, f"{arch}: loss did not descend {first}->{last}"
