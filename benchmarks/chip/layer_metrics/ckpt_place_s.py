"""Seconds of a restore's host-to-device placement of the state until it is
ready, per restore: the program's ckpt.restore.place spans over its
ckpt.restore spans (TrainRunner.init_or_restore)."""
import program_spans


def read(ctx, trace):
    ps = program_spans.read(ctx, trace)
    return ps and ps.per(sum(ps.durations("ckpt.restore.place")),
                         "ckpt.restore")
