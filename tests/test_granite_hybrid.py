"""granite-4.0-h on the serving path against the plain reference
(benchmarks/chip/reference/granite4h.py: jax.numpy in float32 at HIGHEST,
one sequence, the Mamba-2 recurrence as a sequential scan), at the smoke
size with float32 weights from a seed: padded prefill and decode in place
through the engine's calls, served tokens through ServingEngine on both
decode backends, the recurrent state a padded prefill leaves and a reused
slot starts from, the shares of an expert-parallel MoE layer, and the
route counts and tags the benchmark reads."""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.core import trace
from repro.models import forward, init_cache, init_params
from repro.models import moe as moe_mod
from repro.serve.engine import ServingEngine
from test_serving import (admit_both_ways, forced_greedy, prompt_kv,
                          serve_staggered)

_REF = Path(__file__).resolve().parents[1] / "benchmarks" / "chip" / \
    "reference" / "granite4h.py"
_spec = importlib.util.spec_from_file_location("granite4h_reference", _REF)
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

CFG = get("granite_4_0_h_small", smoke=True).replace(param_dtype="float32",
                                                     kv_block_size=8)
PARAMS = init_params(jax.random.PRNGKey(0), CFG)
MAX_SEQ = 32
# float32 on both sides: the program's chunked SSD, blocked attention and
# routing differ from the reference's sequential scan only by reassociation
# (measured 6e-8 on these logits, which reach 0.54); the same program with
# its weights rounded to bfloat16 reads 6e-4 and fails
LOGIT_TOL = 1e-5


def _dims(cfg):
    di = cfg.ssm_expand * cfg.d_model
    kinds = ["mamba" if k == "mamba2_moe" else "attention"
             for k in cfg.pattern_for_layers()]
    return ref.Dims(cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                    cfg.d_ff, cfg.shared_expert_ff, cfg.n_experts,
                    cfg.n_held, cfg.expert_first, cfg.top_k, cfg.vocab_size,
                    tuple(kinds), di // cfg.ssm_head_dim, cfg.ssm_head_dim,
                    cfg.ssm_state, cfg.ssm_conv, cfg.norm_eps,
                    cfg.attn_scale, cfg.embedding_multiplier,
                    cfg.residual_multiplier, cfg.logits_scaling)


def _ref_weights(params, cfg):
    """The program's weights in the reference's layout: one stack of one
    per layer, in layer order."""
    unit, reps = len(cfg.block_pattern), cfg.n_layers // len(
        cfg.block_pattern)
    layers = tuple(jax.tree.map(lambda a: a[r:r + 1], params["layers"][pi])
                   for r in range(reps) for pi in range(unit))
    return dict(params, layers=layers)


DIMS = _dims(CFG)
REF_W = _ref_weights(PARAMS, CFG)


def ref_logits(tokens):
    return np.asarray(ref.logits(REF_W, jnp.asarray(tokens), DIMS))


def _mamba_groups(caches, cfg=CFG):
    return [g for g in caches["layers"] if "ssm" in g]


def test_the_reference_takes_the_programs_init_from_a_seed():
    """The benchmark's reference draws the program's weights itself: the
    same tree and values, bf16, from the seed, both made in one jitted call
    (as the benchmark's driver makes the program's)."""
    cfg = CFG.replace(param_dtype="bfloat16", n_layers=2)
    got = ref.init_weights(7, _dims(cfg))
    want = _ref_weights(jax.jit(init_params, static_argnums=1)(
        jax.random.PRNGKey(7), cfg), cfg)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a),
                                                     np.asarray(b))


@pytest.mark.parametrize("kv_backend", ["reference", "pallas_interpret"])
def test_padded_prefill_and_decode_in_place_match_the_reference(kv_backend):
    """The engine's calls: a prefill padded to max_seq and told each
    prompt's length, then 8 in-place decode steps of teacher-forced
    tokens; every logit against the reference's full forward."""
    rng = np.random.default_rng(1)
    lengths = [13, 5]
    seqs = [rng.integers(0, CFG.vocab_size, n + 8) for n in lengths]
    toks = np.zeros((2, MAX_SEQ), np.int32)
    for b, n in enumerate(lengths):
        toks[b, :n] = seqs[b][:n]
    caches = init_cache(CFG, 2, MAX_SEQ, "paged")
    logits, caches = forward(PARAMS, jnp.asarray(toks), CFG, mode="prefill",
                             caches=caches,
                             lengths=jnp.asarray(lengths, jnp.int32))
    want = [ref_logits(s) for s in seqs]
    for b, n in enumerate(lengths):
        np.testing.assert_allclose(np.asarray(logits[b, n - 1]),
                                   want[b][n - 1], rtol=0, atol=LOGIT_TOL)
    for j in range(8):
        pos = jnp.asarray([n + j for n in lengths], jnp.int32)
        tok = jnp.asarray([[s[n + j]] for s, n in zip(seqs, lengths)])
        logits, caches = forward(PARAMS, tok, CFG, mode="decode",
                                 caches=caches, pos=pos,
                                 kv_backend=kv_backend)
        for b, n in enumerate(lengths):
            np.testing.assert_allclose(np.asarray(logits[b, 0]),
                                       want[b][n + j], rtol=0,
                                       atol=LOGIT_TOL)


def test_bf16_weights_fail_the_logit_tolerance():
    """The tolerance above separates the configuration's precision from the
    one below it: the same prefill with the weights rounded to bf16."""
    bf = jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(a.dtype),
                      PARAMS)
    seq = np.random.default_rng(2).integers(0, CFG.vocab_size, 16)
    logits, _ = forward(bf, jnp.asarray(seq[None]), CFG, mode="train")
    assert np.max(np.abs(np.asarray(logits[0]) - ref_logits(seq))) > \
        10 * LOGIT_TOL


@pytest.mark.parametrize("kv_backend", ["reference", "pallas_interpret"])
def test_served_tokens_are_the_references_greedy_tokens(kv_backend):
    """ServingEngine, 3 requests on 2 slots (one slot reused), compacted
    midway: each served token's reference logit is within twice the logit
    tolerance of the reference's best (a near-tie may go either way)."""
    eng = ServingEngine(CFG, PARAMS, max_slots=2, max_seq=MAX_SEQ, seed=3,
                        kv_backend=kv_backend)
    rng = np.random.default_rng(3)
    reqs = [eng.submit(rng.integers(0, CFG.vocab_size, n).tolist(),
                       max_new=m) for n, m in ((13, 8), (6, 5), (9, 8))]
    for _ in range(4):
        eng.step()
    eng.compact(backend="reference")
    eng.run_until_drained()
    assert all(r.done for r in reqs)
    for r in reqs:
        seq = r.prompt + r.out[:-1]
        lg = ref_logits(seq)[len(r.prompt) - 1:]
        gap = lg.max(-1) - lg[np.arange(len(r.out)), r.out]
        assert gap.max() <= 2 * LOGIT_TOL, (r.rid, gap)


def test_padded_prefill_leaves_the_unpadded_state():
    """Each Mamba layer's SSM state and conv tail after a prefill padded to
    max_seq and told the lengths equal an unpadded prefill's, per
    sequence, lengths short of the conv width included."""
    rng = np.random.default_rng(4)
    lengths = [13, 2]
    toks = rng.integers(0, CFG.vocab_size, (2, MAX_SEQ)).astype(np.int32)
    _, padded = forward(PARAMS, jnp.asarray(toks), CFG, mode="prefill",
                        caches=init_cache(CFG, 2, MAX_SEQ, "paged"),
                        lengths=jnp.asarray(lengths, jnp.int32))
    for b, n in enumerate(lengths):
        _, alone = forward(PARAMS, jnp.asarray(toks[b:b + 1, :n]), CFG,
                           mode="prefill",
                           caches=init_cache(CFG, 1, MAX_SEQ, "dense"))
        for got, want in zip(_mamba_groups(padded), _mamba_groups(alone)):
            for name in ("ssm", "conv"):
                np.testing.assert_allclose(
                    np.asarray(got[name][:, b]), np.asarray(want[name][:, 0]),
                    rtol=1e-5, atol=1e-6)
    # unpadded, the state would differ: the padding is not a no-op
    _, unaware = forward(PARAMS, jnp.asarray(toks), CFG, mode="prefill",
                         caches=init_cache(CFG, 2, MAX_SEQ, "paged"))
    assert not np.allclose(np.asarray(_mamba_groups(unaware)[0]["ssm"]),
                           np.asarray(_mamba_groups(padded)[0]["ssm"]))


def test_a_reused_slot_starts_from_zero_state():
    """A request in a slot that served another gets the state, and the
    tokens, it gets in a fresh engine."""
    prompt = [5, 9, 2, 7, 11, 3]

    def serve(first):
        eng = ServingEngine(CFG, PARAMS, max_slots=1, max_seq=MAX_SEQ,
                            seed=5, scramble_blocks=False)
        if first:
            eng.submit(first, max_new=6)
            eng.run_until_drained()
            assert np.any(np.asarray(_mamba_groups(eng.caches)[0]["ssm"]))
        r = eng.submit(prompt, max_new=6)
        eng.step()                              # admission and one decode
        state = jax.tree.map(np.asarray, _mamba_groups(eng.caches))
        eng.run_until_drained()
        return state, r.out

    state, out = serve([1, 2, 3, 4, 5, 6, 7, 8, 9, 10])
    fresh_state, fresh_out = serve(None)
    assert out == fresh_out
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(fresh_state)):
        np.testing.assert_array_equal(a, b)


# an expert-parallel layer cut into shares as the benchmark's deployment
# cuts granite's: 72 experts, top 10, 8 shares of 9
EP = CFG.replace(n_experts=72, top_k=10)


def _share(params, i):
    cfg = EP.replace(experts_held=9, expert_first=9 * i)
    p = {k: (v[9 * i:9 * i + 9] if k.startswith("experts_") else v)
         for k, v in params.items()}
    return cfg, p


def test_expert_shares_add_up_to_the_uncut_layer():
    """The partial results of the 8 shares, the shared expert counted once,
    add up to the uncut 72-expert layer; each share is the reference's
    share (float32 on both sides)."""
    params = moe_mod.moe_init(jax.random.PRNGKey(6), EP)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 5, EP.d_model))
    whole = moe_mod.moe_apply(params, x, EP, None)
    shared = moe_mod.shared_expert(params, x)
    total = -7 * shared
    for i in range(8):
        cfg, p = _share(params, i)
        part = moe_mod.moe_apply(p, x, cfg, None)
        total = total + part
        want = ref._moe_ffn(p, x.reshape(-1, EP.d_model), _dims(cfg),
                            ref.matmul_f32)
        np.testing.assert_allclose(np.asarray(part).reshape(-1, EP.d_model),
                                   np.asarray(want), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)


def test_route_counts_count_the_held_experts_routings():
    params = moe_mod.moe_init(jax.random.PRNGKey(8), EP)
    x = jax.random.normal(jax.random.PRNGKey(9), (12, EP.d_model))
    mask = jnp.arange(12) < 9
    _, topi, _ = moe_mod._route(x, params["router"], EP.top_k)
    topi = np.asarray(topi)[:9]
    for i in (0, 3, 7):
        cfg, p = _share(params, i)
        held = (topi >= 9 * i) & (topi < 9 * i + 9)
        routes, hit = np.asarray(moe_mod.route_counts(x, p["router"], cfg,
                                                      mask))
        assert routes == held.sum()
        assert hit == len(np.unique(topi[held]))


def test_engine_tags_routes_and_state_bytes(monkeypatch):
    """Under a tracer, each decode step's serve.step span carries the
    routings of active slots to held experts, the held experts hit (both
    summed over layers, from the decode's own outputs) and the recurrent
    state bytes of the active slots read and written; each admission's
    serve.admit span its prompt's routings."""
    t = trace.Tracer()
    monkeypatch.setattr(trace, "_ACTIVE", t)
    eng = ServingEngine(CFG, PARAMS, max_slots=3, max_seq=MAX_SEQ, seed=8)
    eng.submit([3, 1, 4, 1, 5], max_new=3)
    eng.submit([9, 2, 6], max_new=3)
    eng.run_until_drained()
    layers = CFG.n_layers
    steps = [s for s in t.spans if s.name == "serve.step"
             and "moe_routes_local" in (s.tags or {})]
    assert steps
    per_slot = sum(a.nbytes // a.shape[1] for g in _mamba_groups(eng.caches)
                   for a in jax.tree.leaves(g))
    for s in steps:
        active = s.tags["kv_blocks"]        # one block a sequence here
        assert s.tags["moe_routes_local"] == active * layers * CFG.top_k
        assert 0 < s.tags["moe_experts_hit"] <= layers * CFG.n_held
        assert s.tags["ssm_state_bytes"] == 2 * active * per_slot
    admits = [s for s in t.spans if s.name == "serve.admit"]
    assert [s.tags["moe_routes_local"] for s in admits] == \
        [5 * layers * CFG.top_k, 3 * layers * CFG.top_k]


def test_an_attention_moe_admission_counts_only_the_prompt(monkeypatch):
    """An MoE model with no recurrent layer (OLMoE) is told the prompt's
    length too: its serve.admit routings are the prompt's, not the padded
    prefill's."""
    cfg = get("olmoe_1b_7b", smoke=True).replace(param_dtype="float32",
                                                 kv_block_size=8)
    t = trace.Tracer()
    monkeypatch.setattr(trace, "_ACTIVE", t)
    eng = ServingEngine(cfg, init_params(jax.random.PRNGKey(10), cfg),
                        max_slots=1, max_seq=MAX_SEQ, seed=10)
    eng.submit([3, 1, 4, 1, 5, 9], max_new=2)
    eng.run_until_drained()
    admits = [s for s in t.spans if s.name == "serve.admit"]
    assert [s.tags["moe_routes_local"] for s in admits] == \
        [6 * cfg.n_layers * cfg.top_k]


def test_a_bucketed_admission_equals_one_padded_to_max_seq(monkeypatch):
    """Prompts in two buckets below max_seq (1,024: eight SSD chunks)
    leave their slot's first token, K/V up to the prompt's length, every
    Mamba-2 layer's SSM state and conv tail, and serve.admit's route
    count, as a prefill padded to max_seq does."""
    t = trace.Tracer()
    monkeypatch.setattr(trace, "_ACTIVE", t)
    eng = ServingEngine(CFG, PARAMS, max_slots=2, max_seq=1024, seed=9)
    rng = np.random.default_rng(9)
    for n in (100, 300):                            # buckets 128 and 512
        req, got, first, want, routes = admit_both_ways(
            eng, rng.integers(0, CFG.vocab_size, n).tolist())
        assert req.out == [first]
        admit = [s for s in t.spans if s.name == "serve.admit"][-1]
        assert admit.tags["moe_routes_local"] == routes
        for g, w in zip(got["layers"], want["layers"]):
            pairs = (zip(prompt_kv(g, n, CFG.kv_block_size),
                         prompt_kv(w, n, CFG.kv_block_size))
                     if "pool_k" in g else
                     ((g["ssm"], w["ssm"]), (g["conv"], w["conv"])))
            for a, b in pairs:
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kv_backend", ["reference", "pallas_interpret"])
def test_pipelined_served_tokens_are_the_greedy_tokens(kv_backend):
    """One step in flight, admissions mid-stream, a reused slot and a
    compaction: every served token, the last included, is the full
    forward's greedy token."""
    eng = ServingEngine(CFG, PARAMS, max_slots=3, max_seq=MAX_SEQ, seed=11,
                        kv_backend=kv_backend)
    for r in serve_staggered(eng, compact_after=3):
        assert r.out == forced_greedy(CFG, PARAMS, r), r.rid


def test_lagged_route_counts_tag_their_own_step(monkeypatch):
    """Traced, each serve.step that dispatches a decode carries that
    decode's routings (fetched a step later) and in_flight: 0 on the
    first, 1 on each consecutive one.  The active slots fall 3, 2, 1 (one
    KV block each), so a count tagged on a neighbouring step would not
    match its kv_blocks."""
    t = trace.Tracer()
    monkeypatch.setattr(trace, "_ACTIVE", t)
    eng = ServingEngine(CFG, PARAMS, max_slots=3, max_seq=MAX_SEQ, seed=12)
    for m in (2, 4, 6):                     # positions 2-7: one block
        eng.submit([7, 3], max_new=m)
    eng.run_until_drained()
    steps = [s for s in t.spans if s.name == "serve.step"]
    dispatched = [s for s in steps if "kv_blocks" in s.tags]
    assert [s.tags["kv_blocks"] for s in dispatched] == [3, 3, 2, 2, 1, 1]
    assert [s.tags["in_flight"] for s in dispatched] == [0, 1, 1, 1, 1, 1]
    for s in dispatched:
        assert s.tags["moe_routes_local"] == \
            s.tags["kv_blocks"] * CFG.n_layers * CFG.top_k
    # the last step only fetches: no decode of its own to tag
    assert steps[-1].tags == {}
