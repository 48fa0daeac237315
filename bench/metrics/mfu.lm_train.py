"""Model flops of the language-model training window (forward and backward,
remat's recomputation not counted) over the bf16 peak (moves
train_tokens_per_s)."""
from bench.readers import mfu as read  # noqa: F401
