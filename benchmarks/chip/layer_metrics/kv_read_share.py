"""Share of the paged KV pool that the decode step reads: the sum over the
window's serve.step spans of their kv_blocks tags (per layer, the blocks
the paged-attention kernel reads, ceil((pos + 1) / block) of each active
sequence) over the sum of their pool_blocks tags (slots x blocks a slot).
None where the program tags no step so (a decode step that reads the
whole pool)."""
import program_spans


def read(ctx, trace):
    ps = program_spans.read(ctx, trace)
    if ps is None:
        return None
    steps = [s for s in ps.named("serve.step") if "kv_blocks" in s.tags]
    pool = sum(s.tags["pool_blocks"] for s in steps)
    return sum(s.tags["kv_blocks"] for s in steps) / pool if pool else None
