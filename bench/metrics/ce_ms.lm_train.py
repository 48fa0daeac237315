"""Device milliseconds per step of the ops under the program's ``ce`` scope:
the streaming cross-entropy with its LM-head matmuls (moves
train_tokens_per_s)."""
from bench.scopes import ms_per_step


def read(ctx):
    return ms_per_step(ctx, "ce")
