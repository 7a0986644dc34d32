"""Milliseconds per engine step in which chip 0 sat idle under the
program's serve.step span and under no span inside it (so not under
serve.admit): the trace's idle stretches given to the innermost program
span open over them (program_spans.py), over the number of serve.step
spans; 0 where no step ran."""
import program_spans


def read(ctx, trace):
    ps = program_spans.read(ctx, trace)
    if ps is None or ps.offset is None:
        return None
    return 1e3 * ps.per(ps.idle.get("serve.step", 0.0), "serve.step")
