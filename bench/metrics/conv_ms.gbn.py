"""Device milliseconds per step of the ops under the program's ``conv`` scope:
the convolutions (stem and projection shortcuts included), forward and both
gradients, with their NHWC relayouts (moves images_per_s)."""
from bench.scopes import ms_per_step


def read(ctx):
    return ms_per_step(ctx, "conv")
