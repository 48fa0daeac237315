"""Device idle share of a serving window (moves serve_tokens_per_s)."""
from bench.readers import idle_share as read  # noqa: F401
