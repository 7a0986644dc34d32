"""Milliseconds of a save's own manifest commit (JSON, the Raft put, the
file's write and rename), per save: the program's ckpt.store.manifest spans
that no ckpt.store.gc span holds, over its ckpt.save spans."""
import program_spans


def read(ctx, trace):
    ps = program_spans.read(ctx, trace)
    if ps is None:
        return None
    gc = {s.sid for s in ps.named("ckpt.store.gc")}
    own = [s.t1 - s.t0 for s in ps.named("ckpt.store.manifest")
           if s.parent not in gc]
    return 1e3 * ps.per(sum(own), "ckpt.save")
