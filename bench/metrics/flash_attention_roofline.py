"""Roofline share of the flash-attention Pallas kernels in the training
step (moves train_tokens_per_s): forward, remat's recomputed forward and
backward calls, causal, at the cell's rows and sequence length. The
kernels are told from the step's other Pallas calls by their
(rows, heads, seq, head_dim) operands and results."""
from bench.counts import lm
from bench.readers import kernel_roofline


def read(ctx):
    cfg, tr = ctx.cell.config, ctx.cell.traffic
    hd, seq = cfg["head_dim"], tr["seq"]

    def pick(k):
        return any(len(s.dims) == 4 and s.dims[-1] == hd and s.dims[2] == seq
                   for s in k.results)

    per_call = lm.flash_attention(cfg, tr["rows"], seq)
    calls = cfg["num_hidden_layers"] * ctx.outcome.facts["steps"]
    fwd = 2 if tr["remat"] else 1
    return kernel_roofline(
        ctx, "step", pick, (fwd + 1) * calls,
        calls * (fwd * per_call["fwd"]["flops"] + per_call["bwd"]["flops"]),
        calls * (fwd * per_call["fwd"]["bytes"] + per_call["bwd"]["bytes"]))
