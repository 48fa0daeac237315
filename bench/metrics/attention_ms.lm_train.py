"""Device milliseconds per step of the ops under the program's ``attention``
scope: the q/k/v/o projections, qk-norm, RoPE and the flash-attention
kernels, forward, remat's recomputation and backward (moves
train_tokens_per_s)."""
from bench.scopes import ms_per_step


def read(ctx):
    return ms_per_step(ctx, "attention")
