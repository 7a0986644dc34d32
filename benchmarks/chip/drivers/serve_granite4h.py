"""Serving driver of the Granite-4.0-H stage: the program's ServingEngine
under a traffic file's load, as drivers/serve.py drives it, with this
family's mapping, reference and counts.

Set-up, window, compaction and drain are serve.py's (see its docstring):
weights from the seed in one jitted call of the program's own init, one
admission per slot and one compaction to warm every shape, the clients'
first requests admitted before the window opens.  What differs:

  mapping      `program_config`: the published keys (Hugging Face's
               granitemoehybrid config.json names) to the program's
               ModelConfig, the stage's layers as one block period, the
               held experts and the router's width from "expert_parallel".
  compaction   the check reads the cache group that holds the paged KV
               pool (the stage's one attention layer), not the first.
  context      the stage's sizes for the readers that count its bytes and
               FLOPs (layer_metrics/decode_hbm_roofline.py,
               moe_tokens_per_expert.py, mfu.serve.reasoning.py).
  check        reference/granite4h.py replays a seeded sample of finished
               requests: the widest gap of a served token's logit below
               the reference's best.
"""
from __future__ import annotations

import gc
import shutil
import time

import numpy as np

import harness
import traffic as traffic_lib
from reference import granite4h as ref
from spans import Spans
from trace_reduce import Recording

serve = harness.load_module(harness.BENCH_DIR / "drivers" / "serve.py",
                            "driver_serve_helpers")

KINDS = {"mamba": "mamba2_moe", "attention": "attn_moe"}


def program_config(config: dict):
    """The published keys to the program's ModelConfig: one pipeline stage
    of layer_types[:num_hidden_layers], its layers as one period."""
    from repro.configs.base import ModelConfig
    if config["model_type"] != "granitemoehybrid":
        raise ValueError(f"no mapping for model_type "
                         f"{config['model_type']!r}")
    ep = config["expert_parallel"]
    d = config["hidden_size"]
    layers = config["num_hidden_layers"]
    # what the program's blocks compute, and the file must state
    fixed = {"mamba_n_groups": 1, "position_embedding_type": "nope",
             "attention_bias": False, "mamba_proj_bias": False,
             "mamba_conv_bias": True, "hidden_act": "silu",
             "normalization_function": "rmsnorm",
             "mamba_n_heads": config["mamba_expand"] * d
             // config["mamba_d_head"]}
    for key, want in fixed.items():
        if config[key] != want:
            raise ValueError(f"{key} {config[key]!r}: the program has "
                             f"{want!r}")
    return ModelConfig(
        name=config["name"], family="hybrid", n_layers=layers, d_model=d,
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=d // config["num_attention_heads"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        n_experts=ep["router_experts"],
        experts_held=config["num_local_experts"],
        expert_first=ep["first_held_expert"],
        top_k=config["num_experts_per_tok"],
        shared_expert_ff=config["shared_intermediate_size"],
        block_pattern=tuple(KINDS[k] for k in config["layer_types"][:layers]),
        ssm_state=config["mamba_d_state"], ssm_expand=config["mamba_expand"],
        ssm_conv=config["mamba_d_conv"], ssm_head_dim=config["mamba_d_head"],
        nope=True, attn_scale=float(config["attention_multiplier"]),
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        tie_embeddings=config["tie_word_embeddings"],
        norm_eps=float(config["rms_norm_eps"]),
        param_dtype=config["torch_dtype"],
        kv_block_size=config["serving"]["kv_block_size"],
        source=config["source"])


def stage_dims(config: dict) -> dict:
    """The sizes the readers count bytes and FLOPs from."""
    m = ref.dims(config)
    return {"d": m.d, "nh": m.nh, "nkv": m.nkv, "hd": m.hd, "ff": m.ff,
            "shared_ff": m.shared_ff, "experts": m.experts, "held": m.held,
            "vocab": m.vocab, "ssm_heads": m.ssm_heads,
            "ssm_head_dim": m.ssm_head_dim, "ssm_state": m.ssm_state,
            "conv": m.conv, "mamba_layers": m.kinds.count("mamba"),
            "attn_layers": m.kinds.count("attention"),
            "block": config["serving"]["kv_block_size"], "itemsize": 2}


def _attn_group(eng) -> dict:
    return next(g for g in eng.caches["layers"] if "pool_k" in g)


def _served_gap(weights, dims, reqs, max_seq: int, precision: str) -> float:
    """Widest gap, over every served token of `reqs`, between the reference's
    best logit and its logit of the served token (with precision 'fp8', of
    the token the control puts first: the control)."""
    import jax.numpy as jnp
    widest = 0.0
    for r in reqs:
        seq = list(r.prompt) + list(r.out[:-1])
        toks = np.zeros(max_seq, np.int32)
        toks[:len(seq)] = seq
        pos = np.arange(len(r.prompt) - 1, len(seq))
        lg = np.asarray(ref.logits_jit(weights, jnp.asarray(toks), dims,
                                       "f32"))[pos]
        chosen = np.asarray(r.out)
        if precision != "f32":
            chosen = np.asarray(ref.logits_jit(
                weights, jnp.asarray(toks), dims, precision))[pos].argmax(-1)
        gap = lg.max(-1) - lg[np.arange(len(pos)), chosen]
        widest = max(widest, float(gap.max()))
    return widest


def _checked_compaction(eng, backend: str, seed: int, n: int = 16):
    """serve.py's check of the first compaction that moves blocks, on the
    attention group.  Returns (blocks compared, blocks that differ)."""
    group = _attn_group(eng)
    table = np.asarray(group["table"])                # (reps, slots, nblk)
    moved = np.argwhere(table != np.arange(table.shape[-1]))
    rng = np.random.default_rng(np.random.SeedSequence([seed, 11]))
    pick = moved[rng.permutation(len(moved))[:n]]
    before = [serve._read_block(group, l, s, table[l, s, b])
              for l, s, b in pick]
    eng.compact(backend=backend)
    after = _attn_group(eng)
    bad = 0
    for (l, s, b), (k, v) in zip(pick, before):
        k2, v2 = serve._read_block(after, l, s, b)
        bad += int(not np.array_equal(k2, k) or not np.array_equal(v2, v))
    return len(pick), bad


def run(cell, *, seed: int, seconds: float, trace: bool, t0: float,
        control: bool = False):
    import jax
    from repro.models import init_params
    from repro.serve.engine import ServingEngine

    config = cell.config
    serving = config["serving"]
    backend = serving["kv_backend"]
    slots, max_seq = serving["slots"], serving["max_seq"]
    every = serving["compact_every_finished"]
    mcfg = program_config(config)
    s32 = harness.seed32(seed)
    device = jax.devices()[0]
    spans = Spans()
    log = harness.SetupLog(t0)
    log.mark("start")

    # ---------------------------------------------------------- set-up
    params = jax.jit(init_params, static_argnums=1)(jax.random.PRNGKey(s32),
                                                    mcfg)
    jax.block_until_ready(params)
    log.mark("weights")
    eng = ServingEngine(mcfg, params=params, max_slots=slots,
                        max_seq=max_seq, seed=s32)
    del params
    log.mark("engine")
    tr = cell.traffic
    warm = traffic_lib.requests(
        dict(tr, kind="closed_loop", clients=slots, requests_per_client=1,
             output_tokens=[2, 2]), seed, 0, config["vocab_size"], stream=1)
    for r in warm:
        eng.submit(r.prompt, max_new=r.max_new)
    eng.run_until_drained()
    log.mark("warm_admissions")
    eng.compact(backend=backend)
    serve._read_block(_attn_group(eng), 0, 0, 0)     # the check's reads
    log.mark("warm_compaction")
    reqs = traffic_lib.requests(tr, seed, seconds, config["vocab_size"])
    if trace:
        spans.wrap(eng, "step", "bench/engine.step")
        spans.wrap(eng, "compact", "bench/engine.compact")
        spans.wrap(eng, "submit", "bench/engine.submit")
    load = serve._Load()
    finished0 = len(eng.finished)
    compactions0 = eng.compactions

    def submit(r):
        req = eng.submit(r.prompt, max_new=r.max_new)
        load.due[req.rid] = 0.0
        load.reqs[req.rid] = req

    def step(now_fn):
        if trace:
            ctx = int(sum(eng.pos[s] + 1 for s in eng.active))
            queued = list(eng.queue)
        produced = eng.step()
        now = now_fn()
        if trace:
            admitted = [len(q.prompt) for q in queued if q.slot >= 0]
            load.admits += [(now, p) for p in admitted]
            load.steps.append((now, produced,
                               ctx + sum(p + 1 for p in admitted)))
        new = eng.finished[finished0 + load.seen_finished:]
        for q in new:
            load.done[q.rid] = now
        before = load.seen_finished
        load.seen_finished += len(new)
        if load.seen_finished // every > before // every:
            if load.blocks_checked:
                eng.compact(backend=backend)
            else:
                n, bad = _checked_compaction(eng, backend, seed)
                load.blocks_checked, load.blocks_differ = n, bad
        return new

    def tokens_out():
        return sum(len(q.out) for q in load.reqs.values())

    # closed loop: every client's first request is admitted before the
    # window opens
    for r in reqs[:tr["clients"]]:
        submit(r)
    i = len(load.reqs)
    eng.step()
    log.mark("first_admissions")

    # ---------------------------------------------------------- window
    rundir = harness.run_dir(cell.name)
    recording = Recording(str(rundir / "trace"), trace)
    setup_s = log.window_opens()
    tokens0 = tokens_out()
    with recording:
        spans.recording = True
        w0 = time.perf_counter()
        clock = lambda: time.perf_counter() - w0
        while clock() < seconds:
            if not eng.active and not eng.queue and i == len(reqs):
                break                       # every request has been served
            for _ in step(clock):
                if i < len(reqs):
                    submit(reqs[i])
                    i += 1
        jax.block_until_ready(eng.caches)
        window_s = clock()
        log.window_closes()
        spans.recording = False
    tokens = tokens_out() - tokens0
    reduced = recording.reduced(device.platform)
    shutil.rmtree(rundir, ignore_errors=True)
    peak = (device.memory_stats() or {}).get("peak_bytes_in_use")

    # ------------------------------------------------------ after the window
    d0 = time.perf_counter()
    while load.seen_finished < every and \
            time.perf_counter() - d0 < serve.DRAIN_LIMIT_S:
        step(clock)
    served = [load.reqs[rid] for rid in load.done]
    pool = _attn_group(eng)["pool_k"]
    ctx = {"setup": log.summary(), "spans": spans, "steps": load.steps,
           "admits": load.admits,
           "compactions": eng.compactions - compactions0,
           "pool_shape": tuple(pool.shape),
           "pool_itemsize": pool.dtype.itemsize,
           "stage_dims": stage_dims(config),
           "device_kind": device.device_kind, "completed": len(served),
           "completed_in_window": sum(1 for t in load.done.values()
                                      if t <= window_s),
           "window_s": window_s, "tokens": tokens,
           "backlog_at_close": sum(1 for rid in load.due
                                   if rid not in load.done)}
    e2e = {"setup_s": setup_s, "decode_tokens_per_s": tokens / window_s}
    del eng, pool
    gc.collect()

    # ------------------------------------------------------------- check
    dims = ref.dims(config)
    weights = ref.init_weights(s32, dims)
    lengths = [len(r.out) for r in served]
    sample = [served[j] for j in traffic_lib.longest_first_sample(
        lengths, tr["check_requests"], seed)]
    limits = harness.load_limits(cell)
    checks = [
        harness.Check("served_logit_gap",
                      _served_gap(weights, dims, sample, max_seq, "f32"),
                      limits["served_logit_gap"]),
        harness.Check("compaction_blocks_differ",
                      load.blocks_differ + int(load.blocks_checked == 0), 0),
        harness.Check("requests_unfinished", 0, 0),
    ]
    ctx["checked_tokens"] = sum(len(r.out) for r in sample)
    ctx["control"] = ({"served_logit_gap": _served_gap(
        weights, dims, sample, max_seq, "fp8")} if control else {})
    return harness.Outcome(setup_s, e2e, len(load.due), 0, checks, peak,
                           ctx, reduced)
