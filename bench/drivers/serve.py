"""Window driver for serving through ``ContinuousEngine``: an open loop of
seeded requests arriving on the engine's decode-step clock
(:mod:`bench.requests`), admitted as slots free up, decoded greedily.

The window is held to ``--seconds`` of wall clock by driving the engine's
loop a tick at a time, as ``ContinuousEngine.run`` does, through
``submit``, ``_admit_ready`` and ``step`` and the ``clock``, ``active``,
``queue``, ``tokens_out``, ``completions`` and ``_generated`` it keeps.

Traffic keys: ``slots``, ``max_len``, ``page_size``, ``total_pages``,
``cache_dtype``,
``use_kernels``, ``rate_per_step``, ``block``, ``prompt_lens``,
``new_tokens``, ``settle_steps`` (steps served before the window opens,
so that it opens on a loaded engine), ``check_tokens`` (served tokens the
check compares at least) and ``limits``.
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import time

import jax
import numpy as np

from bench import requests
from bench.counts import lm as counts
from bench.drivers.train_lm import model_config
from bench.harness import Check, Outcome


class Loop:
    """One tick of ``ContinuousEngine.run``'s loop at a time: release the
    requests whose arrival the clock has passed, admit what fits, decode
    one step (or, with nothing to decode, jump the clock to the next
    arrival). Keeps the wall time each request was released and got its
    first token."""

    def __init__(self, eng, reqs, start: float = 0.0):
        self.eng = eng
        self.reqs = (dataclasses.replace(r, arrival=r.arrival + start)
                     for r in reqs)
        self.next = next(self.reqs)
        self.released, self.first, self.meta = {}, {}, {}

    def tick(self) -> None:
        from repro.serving.engine import Request
        eng = self.eng
        while self.next.arrival <= eng.clock:
            r = self.next
            eng.submit(Request(id=r.id, prompt=r.prompt,
                               max_new_tokens=r.max_new_tokens,
                               arrival=r.arrival))
            self.released[r.id] = time.perf_counter()
            self.meta[r.id] = (r.prompt, r.max_new_tokens)
            self.next = next(self.reqs)
        waiting = [r.id for r in eng.queue]
        eng._admit_ready()
        now = time.perf_counter()
        left = {r.id for r in eng.queue}
        for rid in waiting:
            if rid not in left:
                self.first[rid] = now
        if eng.active.any():
            eng.step()
        elif not eng.queue:
            eng.clock = max(eng.clock, self.next.arrival)

    def generated(self, rid: int) -> int:
        eng = self.eng
        if rid in eng.completions:
            return len(eng.completions[rid].tokens)
        return len(eng._generated.get(rid, ()))


def engine(cfg, tr, params, obs=None):
    from repro.serving import ContinuousEngine
    return ContinuousEngine(
        params, model_config(cfg), num_slots=tr["slots"],
        max_len=tr["max_len"], layout="paged", page_size=tr["page_size"],
        total_pages=tr["total_pages"], cache_dtype=tr["cache_dtype"],
        use_kernels=tr["use_kernels"], obs=obs)


def warm(eng, tr) -> None:
    """Compile every program the traffic uses: one admission per prompt
    length, the decode step and the block-table write. The warm-up's
    requests have negative ids and leave the engine empty."""
    lengths = requests.prompt_lengths(tr)
    reqs = iter([requests.Req(-1 - i, np.ones(L, np.int32), 2, 0.0)
                 for i, L in enumerate(lengths)]
                + [requests.Req(-100, np.ones(1, np.int32), 1,
                                float("inf"))])
    loop = Loop(eng, reqs)
    while len(eng.completions) < len(lengths):
        loop.tick()
    jax.block_until_ready(eng.cache)


def sample(loop, done, seed: int, want_tokens: int):
    """A seeded sample of the requests finished in the window, the longest
    among them, holding at least ``want_tokens`` served tokens."""
    eng = loop.eng
    longest = max(done, key=lambda r: len(loop.meta[r][0])
                  + len(eng.completions[r].tokens))
    order = [longest] + [r for r in np.random.default_rng(
        [seed, 4]).permutation(sorted(done)).tolist() if r != longest]
    out, n = [], 0
    for rid in order:
        out.append(rid)
        n += len(eng.completions[rid].tokens)
        if n >= want_tokens:
            break
    return out


def reference_gaps(ref, cfg, tr, params, served, compute="f32",
                   against=None):
    """The widest gap, over every served token of ``served`` ([(prompt,
    tokens)]), between the reference's best logit and that of the token
    served (``compute="fp8"``: the token the control puts first). Returns
    (widest gap, reference logits per request)."""
    max_new = max(int(v) for v, _ in tr["new_tokens"])
    scorer = ref.Scorer(cfg, tr["max_len"], max_new, compute)
    worst, logits = 0.0, []
    for i, (prompt, toks) in enumerate(served):
        lg = np.asarray(scorer.logits(params, prompt, toks))
        logits.append(lg)
        if against is None:
            gaps = ref.served_gaps(lg, toks)
        else:
            gaps = ref.served_gaps(against[i], lg.argmax(-1))
        worst = max(worst, float(gaps.max()))
    return worst, logits


def run(cell) -> Outcome:
    cfg, tr = cell.config, cell.traffic
    from repro.obs import Observability
    obs = Observability(trace=True, annotate_device=True) if cell.trace \
        else None
    # the engine and the reference take their weights from one compiled
    # call, so that both hold the same bits
    make_weights = jax.jit(lambda k: cell.reference.init(k, cfg))
    key = jax.random.PRNGKey(cell.seed)
    eng = engine(cfg, tr, make_weights(key), obs)
    warm(eng, tr)
    loop = Loop(eng, requests.stream(tr, cfg["vocab_size"], cell.seed),
                start=eng.clock)
    steps0 = eng.steps
    while eng.steps < steps0 + tr["settle_steps"]:
        loop.tick()
    if obs is not None:
        obs.tracer.clear()
    done0 = set(eng.completions)
    gen0 = {rid: loop.generated(rid) for rid in loop.released}
    tok0 = eng.tokens_out
    with cell.window() as w:
        while w.open():
            loop.tick()
        jax.block_until_ready(eng.cache)
        t_end = time.perf_counter()
    t0 = w.t0
    tokens = eng.tokens_out - tok0
    in_window = [rid for rid, t in loop.released.items() if t >= t0]
    ttft = [loop.first.get(rid, t_end) - loop.released[rid]
            for rid in in_window]
    ttft_p95 = float(np.percentile(ttft, 95)) * 1e3
    print(f"serve: {len(ttft)} requests released in the window, "
          f"{sum(rid not in loop.first for rid in in_window)} still "
          f"waiting; {tokens} tokens in {cell.window_s:.3f} s",
          file=sys.stderr)

    flops = 0.0
    for rid, (prompt, _) in loop.meta.items():
        if t0 <= loop.first.get(rid, -1.0):
            flops += counts.prefill_flops(cfg, len(prompt))
        lo, hi = max(gen0.get(rid, 0), 1), loop.generated(rid)
        for i in range(lo, hi):      # the (i+1)-th token, from a decode step
            flops += counts.forward_flops_per_token(cfg, len(prompt) + i)
    spans = []
    if obs is not None:
        o = obs.tracer.origin_ns / 1e9
        spans = [(e["name"], o + e["ts"] / 1e6, o + (e["ts"] + e["dur"]) / 1e6)
                 for e in obs.tracer.events if e.get("ph") == "X"]
    done = [rid for rid in eng.completions if rid not in done0]
    picked = sample(loop, done, cell.seed, tr["check_tokens"])
    served = [(loop.meta[rid][0], np.asarray(eng.completions[rid].tokens))
              for rid in picked]
    del eng, loop
    gc.collect()

    gap, _ = reference_gaps(cell.reference, cfg, tr, make_weights(key),
                            served)
    print(f"serve: compared {sum(len(t) for _, t in served)} served tokens "
          f"of {len(served)} requests", file=sys.stderr)
    facts = {"items": 1, "flops_per_item": flops, "spans": spans,
             "window": (t0, t_end), "hlo": {}, "served": served,
             "weights": lambda: make_weights(key)}
    return Outcome({"serve_tokens_per_s": tokens / cell.window_s,
                    "ttft_p95_ms": ttft_p95},
                   len(in_window), 0, [Check("gap", gap, tr["limits"]["gap"])],
                   facts)


def calibrate(spec, ref, seeds, seconds: float = 20.0):
    """Readings at the cell's own size and load, seed by seed: the widest
    gap of the program's served tokens, and of the control's first choices
    (the reference with fp8 matmuls) at the same positions."""
    from bench import harness
    cfg, tr = spec.config, spec.traffic
    for seed in seeds:
        cell = harness.Cell(spec, seed, seconds, False, jax.devices()[:1],
                            time.perf_counter())
        out = run(cell)
        served, weights = out.facts["served"], out.facts["weights"]
        _, want = reference_gaps(ref, cfg, tr, weights(), served)
        control, _ = reference_gaps(ref, cfg, tr, weights(), served, "fp8",
                                    want)
        yield {"seed": seed, "program": {"gap": out.checks[0].value},
               "control": {"gap": control},
               "tokens": int(sum(len(t) for _, t in served))}
