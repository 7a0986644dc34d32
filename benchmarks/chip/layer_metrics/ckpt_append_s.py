"""Seconds a save spends serializing the state and appending it to the value
log, per save: the program's ckpt.store.append spans
(NezhaCheckpointStore.save) over its ckpt.save spans."""
import program_spans


def read(ctx, trace):
    ps = program_spans.read(ctx, trace)
    return ps and ps.per(sum(ps.durations("ckpt.store.append")), "ckpt.save")
