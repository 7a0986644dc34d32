"""Share of the HBM roofline that the hybrid stage's decode step reaches:
the bytes a step must move, summed over the window's serve.step spans,
over the chip's HBM bandwidth, divided by the device time of the runs of
jit_decode_fn in the window.  A step's bytes: every weight matrix of the
stage read once, of the experts held only those that the span's
moe_experts_hit tag counts; the recurrent state of its active slots read
and written (ssm_state_bytes); and the K and V blocks the paged-attention
kernel must read (kv_blocks per attention layer).  None where the program
tags no step so, or runs no jit_decode_fn."""
import peaks
import program_spans


def stage_matrix_bytes(m):
    """(bytes of the matrices every step reads, bytes of one held expert):
    Mamba-2 in_proj, conv and out_proj; attention's q, k, v, o; each
    layer's router (float32) and shared expert; the tied head's slice."""
    d, w = m["d"], m["itemsize"]
    di = m["ssm_heads"] * m["ssm_head_dim"]
    n = m["ssm_state"]
    mamba = d * (2 * di + 2 * n + m["ssm_heads"]) \
        + m["conv"] * (di + 2 * n) + di * d
    attn = 2 * d * m["nh"] * m["hd"] + 2 * d * m["nkv"] * m["hd"]
    layers = m["mamba_layers"] + m["attn_layers"]
    ffn = 3 * d * m["shared_ff"] * w + d * m["experts"] * 4
    dense = (m["mamba_layers"] * mamba + m["attn_layers"] * attn
             + m["vocab"] * d) * w + layers * ffn
    return dense, 3 * d * m["ff"] * w


def read(ctx, trace):
    ps = program_spans.read(ctx, trace)
    ev = trace.module_events(r"^jit_decode_fn$") if trace is not None else []
    if ps is None or not ev or "stage_dims" not in ctx:
        return None
    steps = [s for s in ps.named("serve.step")
             if "moe_experts_hit" in s.tags]
    if not steps:
        return None
    m = ctx["stage_dims"]
    dense, expert = stage_matrix_bytes(m)
    block = 2 * m["block"] * m["nkv"] * m["hd"] * m["itemsize"]   # K and V
    need = sum(dense + s.tags["moe_experts_hit"] * expert
               + s.tags["ssm_state_bytes"]
               + m["attn_layers"] * s.tags["kv_blocks"] * block
               for s in steps)
    bw = peaks.peaks_for(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * need / bw / sum(e.dur for e in ev)
