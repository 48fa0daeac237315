"""Device idle share of a GBN training window (moves images_per_s)."""
from bench.readers import idle_share as read  # noqa: F401
