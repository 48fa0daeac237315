"""Prefill and decode flops of the requests served in the window, from each
request's own prompt and generated lengths (attention over each token's
context included, dead lanes not), over the window's time and the bf16
peak (moves serve_tokens_per_s)."""
from bench.readers import mfu as read  # noqa: F401
