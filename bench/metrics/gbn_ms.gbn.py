"""Device milliseconds per step of the ops under the program's ``gbn`` scope:
ghost batch norm: the Pallas kernels forward and backward, the lane fold and
its undoing, the ghost reshapes and the running statistics (moves
images_per_s)."""
from bench.scopes import ms_per_step


def read(ctx):
    return ms_per_step(ctx, "gbn")
