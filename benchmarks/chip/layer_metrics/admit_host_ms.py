"""Milliseconds of one admission on the host clock (slot read, fresh block
tables, prefill dispatch, the first token's sync, slot write): the mean of
the program's serve.admit spans; 0 where none ran."""
import program_spans


def read(ctx, trace):
    ps = program_spans.read(ctx, trace)
    return ps and 1e3 * ps.per(sum(ps.durations("serve.admit")),
                               "serve.admit")
