"""Sharding rules unit tests, the refusal of a decode under a mesh, and an
end-to-end multi-device train step run in a subprocess (device count must
be set before jax init)."""
import json
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get
from repro.launch.mesh import make_host_mesh
from repro.models import forward, init_cache, init_params
from repro.sharding.rules import fit_spec, make_rules, param_spec
from jax.sharding import AxisType, PartitionSpec as P


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


def test_a_sharded_decode_is_refused():
    """Decode is unsharded: under a mesh it raises rather than fall back
    to a different algorithm."""
    cfg = get("qwen3_8b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    caches = init_cache(cfg, 2, 32, "paged")
    with pytest.raises(NotImplementedError, match="R8"):
        forward(params, jnp.zeros((2, 1), jnp.int32), cfg,
                make_rules(make_host_mesh()), mode="decode", caches=caches,
                pos=jnp.array([3, -1], jnp.int32))


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
