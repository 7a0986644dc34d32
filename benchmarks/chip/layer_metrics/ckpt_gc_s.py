"""Seconds of the store's GC per save (reading back and rewriting the live
checkpoints, their manifests, the clean-up): the program's ckpt.store.gc
spans over its ckpt.save spans; 0 where no save ran a GC."""
import program_spans


def read(ctx, trace):
    ps = program_spans.read(ctx, trace)
    return ps and ps.per(sum(ps.durations("ckpt.store.gc")), "ckpt.save")
