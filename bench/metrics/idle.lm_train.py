"""Device idle share of a language-model training window (moves
train_tokens_per_s)."""
from bench.readers import idle_share as read  # noqa: F401
