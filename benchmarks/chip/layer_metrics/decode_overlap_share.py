"""Share of the window's decode dispatches made while the previous decode's
tokens were still on the device: the mean of the in_flight tags (1 or 0)
over the program's serve.step spans that carry one (each step that
dispatched a decode).  None where the program tags no step so (an engine
that fetches each step's tokens before the next dispatch)."""
import program_spans


def read(ctx, trace):
    ps = program_spans.read(ctx, trace)
    if ps is None:
        return None
    flags = [s.tags["in_flight"] for s in ps.named("serve.step")
             if "in_flight" in s.tags]
    return sum(flags) / len(flags) if flags else None
