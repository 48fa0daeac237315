"""Median of the engine's ``serve.decode_step`` spans in the window, in ms
(moves serve_tokens_per_s)."""
from bench.readers import program_spans


def read(ctx):
    import statistics
    spans = program_spans(ctx, "serve.decode_step")
    if not spans:
        return None
    return 1e3 * statistics.median(t1 - t0 for t0, t1 in spans)
