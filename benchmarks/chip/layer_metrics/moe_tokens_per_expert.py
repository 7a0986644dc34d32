"""Tokens each held expert computes per layer and decode step: the window's
serve.step spans' moe_routes_local tags (routings of active slots to the
experts held here, summed over the MoE layers) over the number of such
steps x MoE layers x experts held.  None where the program tags no step
so."""
import program_spans


def read(ctx, trace):
    ps = program_spans.read(ctx, trace)
    if ps is None or "stage_dims" not in ctx:
        return None
    steps = [s for s in ps.named("serve.step")
             if "moe_routes_local" in s.tags]
    if not steps:
        return None
    m = ctx["stage_dims"]
    layers = m["mamba_layers"] + m["attn_layers"]
    routes = sum(s.tags["moe_routes_local"] for s in steps)
    return routes / (len(steps) * layers * m["held"])
