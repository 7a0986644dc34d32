"""Share of the bandwidth roofline that the decode step's paged-attention
kernel reaches: the KV bytes it must read in the window (each block that
the serve.step spans' kv_blocks tags count, K and V, in every layer:
blocks x block x kv heads x head dim x itemsize x 2 x layers) over the
chip's HBM bandwidth, divided by the device time of the kernel's own
custom calls (by its name, paged_decode_attention; the token write is a
kernel of its own and is left out) in jit_decode_fn.  None where the
program has no such kernel or tags."""
import peaks
import program_spans

KERNEL = r"^jit_decode_fn:%paged_decode_attention\.\d+ = .* custom-call$"


def read(ctx, trace):
    ps = program_spans.read(ctx, trace)
    ev = trace.op_events(KERNEL)
    if ps is None or not ev:
        return None
    blocks = sum(s.tags.get("kv_blocks", 0) for s in ps.named("serve.step"))
    layers, _, _, bs, nkv, hd = ctx["pool_shape"]
    need = 2 * layers * blocks * bs * nkv * hd * ctx["pool_itemsize"]
    bw = peaks.peaks_for(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * need / bw / sum(e.dur for e in ev) if blocks else None
