"""Roofline share of the ghost batch norm Pallas kernels (moves
images_per_s): their least time at the model's stage shapes over their
device time. Only the GBN kernels are Pallas calls in the vision step."""
from bench.counts import vision
from bench.readers import kernel_roofline


def read(ctx):
    cfg, tr = ctx.cell.config, ctx.cell.traffic
    per_step = vision.gbn_step(cfg, tr["batch"])
    steps = ctx.outcome.facts["steps"]
    return kernel_roofline(ctx, "step", lambda k: True,
                           per_step["calls"] * steps,
                           per_step["flops"] * steps,
                           per_step["bytes"] * steps)
