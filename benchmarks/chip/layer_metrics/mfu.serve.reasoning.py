"""Model FLOPs the hybrid stage's served tokens needed in the traced window
over the chip's bf16 peak for the window.  Per token: the Mamba-2 mixers'
projections and conv and the state update and read-out (4 x heads x head
dim x state a layer), attention's projections and its scores and values
over the token's context, each layer's router and shared expert, and the
head's slice; per (token, expert) routing to a held expert, that expert's
SwiGLU (the moe_routes_local tags of the window's serve.step and
serve.admit spans).  Prefill counts each admitted prompt at its own
length, causal attention over it and the head at its last position."""
import peaks
import program_spans


def token_flops(m):
    """FLOPs of one token through the stage, routed experts and attention
    over context left out."""
    d = m["d"]
    di = m["ssm_heads"] * m["ssm_head_dim"]
    n = m["ssm_state"]
    mamba = 2 * (d * (2 * di + 2 * n + m["ssm_heads"]) + di * d) \
        + 2 * m["conv"] * (di + 2 * n) + 4 * di * n
    attn = 2 * (2 * d * m["nh"] * m["hd"] + 2 * d * m["nkv"] * m["hd"])
    layers = m["mamba_layers"] + m["attn_layers"]
    ffn = 2 * d * m["experts"] + 6 * d * m["shared_ff"]
    return m["mamba_layers"] * mamba + m["attn_layers"] * attn \
        + layers * ffn


def read(ctx, trace):
    ps = program_spans.read(ctx, trace)
    if ps is None or "stage_dims" not in ctx:
        return None
    m = ctx["stage_dims"]
    end = ctx["window_s"]                  # the traced window is the window
    routed = 6 * m["d"] * m["ff"]
    per_ctx = 4 * m["nh"] * m["hd"] * m["attn_layers"]
    head = 2 * m["d"] * m["vocab"]
    tagged = [s for s in ps.named("serve.step") + ps.named("serve.admit")
              if "moe_routes_local" in s.tags]
    if not tagged:
        return None
    flops = routed * sum(s.tags["moe_routes_local"] for s in tagged)
    flops += sum(p * token_flops(m) + per_ctx * p * p / 2 + head
                 for t, p in ctx["admits"] if t <= end)
    for t, n, ctx_sum in ctx["steps"]:
        if t <= end:
            flops += n * (token_flops(m) + head) + ctx_sum * per_ctx
    peak = peaks.peaks_for(ctx["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops / (trace.window_s * peak)
