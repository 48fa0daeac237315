"""Whole-step model flops of the vision training window over the bf16 peak
(moves images_per_s). At the TPU's default precision each f32 convolution
runs as one bf16 pass, so the bf16 peak is the ceiling."""
from bench.readers import mfu as read  # noqa: F401
