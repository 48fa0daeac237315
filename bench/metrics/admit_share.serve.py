"""Per cent of the window covered by the engine's ``serve.admit`` spans,
the batch-1 admission prefills that stall decode (moves ttft_p95_ms)."""
from bench import trace as T
from bench.readers import program_spans


def read(ctx):
    lo, hi = ctx.outcome.facts["window"]
    spans = program_spans(ctx, "serve.admit")
    return 100.0 * sum(b - a for a, b in T.union(spans, lo, hi)) / (hi - lo)
