"""95th percentile of the seconds a request waited in the engine's queue,
over the requests admitted in the window: the program's serve.queued spans
(submit to admission, from the request's own stamps); 0 where none was."""
import program_spans
import stats


def read(ctx, trace):
    ps = program_spans.read(ctx, trace)
    if ps is None:
        return None
    waits = ps.durations("serve.queued")
    return stats.percentile(waits, 95) if waits else 0.0
