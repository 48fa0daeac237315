"""The general generator of serving traffic, read from a traffic file.

Requests arrive as a Poisson process on the engine's decode-step clock (the
semantics of ``serving.engine.poisson_trace`` and of
``launch/serve.py --continuous``), at ``rate_per_step`` requests a step.
Prompt and output lengths come from fixed choice sets with probabilities.

So that every seed offers the same work, the stream comes in blocks of
``block`` requests: each block holds exactly ``p * block`` requests of each
prompt length and of each output length, and ``block`` inter-arrival gaps
at the midpoint quantiles of the exponential distribution; the seed
shuffles their order and pairing and draws the tokens.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Iterator, List

import numpy as np


@dataclasses.dataclass
class Req:
    id: int
    prompt: np.ndarray
    max_new_tokens: int
    arrival: float          # decode steps


def _counts(choices: List[List[float]], block: int) -> List[int]:
    """Each value of a [value, probability] list, repeated p * block times."""
    out: List[int] = []
    for value, p in choices:
        n = p * block
        if abs(n - round(n)) > 1e-9:
            raise ValueError(f"probability {p} does not split a block of "
                             f"{block} requests evenly")
        out += [int(value)] * int(round(n))
    if len(out) != block:
        raise ValueError(f"probabilities sum to {len(out)}/{block}")
    return out


def stream(tr: Dict, vocab: int, seed: int) -> Iterator[Req]:
    """The endless seeded request stream of a traffic file."""
    block = tr["block"]
    prompts = np.array(_counts(tr["prompt_lens"], block))
    outputs = np.array(_counts(tr["new_tokens"], block))
    gaps = np.array([-math.log(1.0 - (k + 0.5) / block)
                     for k in range(block)]) / tr["rate_per_step"]
    rng = np.random.default_rng([seed, 3])
    t, i = 0.0, 0
    while True:
        lp, lo, lg = (rng.permutation(a) for a in (prompts, outputs, gaps))
        for k in range(block):
            t += float(lg[k])
            yield Req(i, rng.integers(0, vocab, int(lp[k]), dtype=np.int32),
                      int(lo[k]), t)
            i += 1


def prompt_lengths(tr: Dict) -> List[int]:
    return sorted({int(v) for v, _ in tr["prompt_lens"]})
