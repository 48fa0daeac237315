"""Counts for the decoder language models: model flops of training and of
serving, and the flash-attention kernel's flops and bytes. Sizes are read
from a configuration file under their Hugging Face names."""
from __future__ import annotations

from typing import Dict

BF16, F32 = 2, 4


def _sizes(cfg: Dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    ff = cfg["intermediate_size"]
    return d, h, kv, hd, ff


def matmul_params_per_layer(cfg: Dict) -> int:
    d, h, kv, hd, ff = _sizes(cfg)
    return 2 * d * h * hd + 2 * d * kv * hd + 3 * d * ff


def forward_flops_per_token(cfg: Dict, context: float) -> float:
    """One token's forward pass through the layers and the vocabulary head,
    attending to ``context`` positions (itself included)."""
    d, h, _, hd, _ = _sizes(cfg)
    layer = 2.0 * matmul_params_per_layer(cfg) + 4.0 * h * hd * context
    return cfg["num_hidden_layers"] * layer + 2.0 * d * cfg["vocab_size"]


def train_flops_per_token(cfg: Dict, seq: int) -> float:
    """Forward and backward (three times the forward) of one token of a
    causal sequence of ``seq`` tokens, where a token attends on average to
    half the sequence. Recomputation under remat is not counted."""
    return 3.0 * forward_flops_per_token(cfg, seq / 2.0)


def prefill_flops(cfg: Dict, prompt: int) -> float:
    """Admission prefill of one prompt: every position through the layers,
    causal attention, and the head at the last position only."""
    d, h, _, hd, _ = _sizes(cfg)
    layers = cfg["num_hidden_layers"] * (
        2.0 * matmul_params_per_layer(cfg) * prompt
        + 2.0 * h * hd * prompt * prompt)
    return layers + 2.0 * d * cfg["vocab_size"]


def flash_attention(cfg: Dict, rows: int, seq: int) -> Dict[str, Dict]:
    """Flops and bytes of one causal flash-attention call over a
    (rows, seq) batch, forward and backward."""
    _, h, kv, hd, _ = _sizes(cfg)
    qk_pv = 2.0 * rows * h * hd * seq * seq       # two causal matmuls
    q = rows * h * seq * hd * BF16
    kvb = 2 * rows * kv * seq * hd * BF16
    lse = rows * h * seq * F32
    return {
        # read q, k, v; write out, lse
        "fwd": {"flops": qk_pv, "bytes": 2 * q + kvb + lse},
        # recompute q k^T, then dv, dp, dq, dk: five causal matmuls; read
        # q, k, v, out, dout, lse; write dq, dk, dv
        "bwd": {"flops": 2.5 * qk_pv, "bytes": 4 * q + 2 * kvb + lse},
    }
