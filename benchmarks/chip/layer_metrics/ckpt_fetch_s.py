"""Seconds of a save's device-to-host fetch of the state, per save: the
program's ckpt.save.fetch spans over its ckpt.save spans (TrainRunner.run)."""
import program_spans


def read(ctx, trace):
    ps = program_spans.read(ctx, trace)
    return ps and ps.per(sum(ps.durations("ckpt.save.fetch")), "ckpt.save")
