"""Milliseconds per compaction in which chip 0 sat idle under the
program's serve.compact span (the relayout's and the kernel's dispatch on
the host): the trace's idle stretches given to the innermost program span
open over them (program_spans.py), over the number of serve.compact spans;
0 where no compaction ran."""
import program_spans


def read(ctx, trace):
    ps = program_spans.read(ctx, trace)
    if ps is None or ps.offset is None:
        return None
    return 1e3 * ps.per(ps.idle.get("serve.compact", 0.0), "serve.compact")
