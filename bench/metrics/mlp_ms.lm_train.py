"""Device milliseconds per step of the ops under the program's ``mlp`` scope:
the SwiGLU kernels, the down projection and the weight gradients (moves
train_tokens_per_s)."""
from bench.scopes import ms_per_step


def read(ctx):
    return ms_per_step(ctx, "mlp")
