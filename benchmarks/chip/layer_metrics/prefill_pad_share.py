"""Share of the prefilled tokens that are padding: the sum over the
program's serve.admit spans of their padded_tokens less prompt_tokens tags,
over the sum of padded_tokens; 0 where no admission ran."""
import program_spans


def read(ctx, trace):
    ps = program_spans.read(ctx, trace)
    if ps is None:
        return None
    admits = ps.named("serve.admit")
    padded = sum(s.tags["padded_tokens"] for s in admits)
    prompt = sum(s.tags["prompt_tokens"] for s in admits)
    return (padded - prompt) / padded if padded else 0.0
