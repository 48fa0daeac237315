"""Cells of the benchmark cut to a size the CPU runs in seconds, and the
patches that let a run proceed without a chip (for the tests only)."""
from __future__ import annotations

import copy

import jax

from bench import harness

TINY_VISION = dict(channels=[4, 8, 16], blocks_per_stage=1,
                   input_shape=[8, 8, 3])
# in float32, so that a sound tiny run keeps inside the limits set for the
# bf16 cell at its own size (a tiny bf16 model's rounding is larger)
TINY_LM = dict(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
               head_dim=32, intermediate_size=256, vocab_size=500,
               num_hidden_layers=2, layout={"embed_rows": 512},
               torch_dtype="float32")

# Pallas kernels run in interpret mode off the chip, too slowly for a test
# run; the tiny cells take the program's jnp path (the kernels have tests
# of their own)
TRAFFIC = {
    "resnet44.lb4096-gbn": dict(batch=16, ghost=4, dataset_size=64,
                                reference_rows=8, use_kernels=False),
    "qwen3-1.7b.train-8x2048": dict(rows=2, seq=64, ce_chunk=128,
                                    base_batch=2, use_kernels=False),
    "qwen3-1.7b.serve-chat": dict(
        slots=4, max_len=64, total_pages=17,
        prompt_lens=[[8, 0.5], [16, 0.5]],
        new_tokens=[[4, 0.5], [8, 0.5]], block=10, rate_per_step=0.3,
        settle_steps=10, check_tokens=20, use_kernels=False),
}


# a cell whose files are here but which BENCHMARK.json does not hold yet:
# (configuration file, traffic, end-to-end metrics)
NOT_YET = {
    "qwen3-1.7b.serve-chat": (
        "bench/configs/qwen3-1.7b.json", "serve-chat",
        [{"name": "serve_tokens_per_s", "unit": "tokens/s"},
         {"name": "ttft_p95_ms", "unit": "ms"},
         {"name": "setup_s", "unit": "s"}]),
}


def _spec(name: str) -> harness.CellSpec:
    if name not in NOT_YET:
        return harness.load_cell(name)
    config, traffic, e2e = NOT_YET[name]
    root = harness.ROOT
    return harness.CellSpec(
        name, 1, harness.load_json(f"{root}/{config}"),
        harness.load_json(f"{root}/bench/traffic/{traffic}.json"), e2e, [])


def cell(name: str) -> harness.CellSpec:
    """The cell ``name`` at a tiny size."""
    spec = copy.deepcopy(_spec(name))
    spec.config.update(TINY_VISION if spec.config.get("kind") == "resnet"
                       else TINY_LM)
    spec.traffic.update(TRAFFIC[name])
    return spec


def off_chip(monkeypatch) -> None:
    """Let a run proceed on the CPU (never on a real run's path)."""
    monkeypatch.setattr(harness, "require_devices",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "peaks_of", lambda kind: {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
