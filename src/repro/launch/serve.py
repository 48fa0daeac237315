"""Serving launcher: batched generation against a (reduced or full)
architecture — the runnable counterpart of the decode dry-run shapes.

Usage:
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b-reduced \
        --batch 8 --prompt-len 16 --max-new 32 [--use-kernels] \
        [--temperature 0.8 --top-k 40] [--prompt-lens 5,16,9,...]

Reports cold (incl. compile) and warm (post-compile) tok/s; ``--use-kernels``
routes prefill through the fused flash-attention forward and decode through
the flash-decode Pallas kernel over a head-major cache.

``--continuous`` instead drives the continuous-batching engine
(:class:`repro.serving.ContinuousEngine`) under a synthetic Poisson arrival
trace (``--rate`` requests per decode step, ``--requests`` total) with a
paged KV cache (``--page-size``, ``--slots``), and reports sustained
useful AND raw tok/s (raw counts dead retired-lane decodes; the gap is the
engine's dropped work) plus the static lockstep baseline over the same
trace at equal cache memory.

Observability: ``--trace out.json`` writes a Chrome/Perfetto-loadable span
trace of the serving loop, ``--metrics-out out.jsonl`` the metrics registry
(for ``--continuous`` that includes the SLO set: TTFT/ITL/e2e percentiles,
queue depth, slot occupancy, page-pool utilization), and
``--device-trace LOGDIR`` captures a ``jax.profiler`` device trace whose
XLA activity lines up under the host spans.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs.registry import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.obs import NULL_TRACER, Observability
from repro.obs.trace import device_trace
from repro.serving import (ContinuousEngine, generate, poisson_trace,
                           run_static_trace)


def _write_obs(args, obs=None) -> None:
    if obs is None:
        return
    obs.write(args.trace, args.metrics_out)
    if args.trace:
        print(f"wrote span trace -> {args.trace} "
              "(load in ui.perfetto.dev or chrome://tracing)")
    if args.metrics_out:
        print(f"wrote metrics JSONL -> {args.metrics_out}")
    table = obs.summary()
    if table:
        print(table)


def _run_continuous(params, cfg, args, *, obs=None) -> None:
    max_len = args.max_len or 4 * args.prompt_len
    max_len = -(-max_len // args.page_size) * args.page_size
    reqs = poisson_trace(
        cfg, args.requests, rate=args.rate, seed=args.seed,
        prompt_len_choices=(args.prompt_len // 2, args.prompt_len),
        new_token_choices=(args.max_new // 2, args.max_new))
    n_blocks = max_len // args.page_size
    eng = ContinuousEngine(
        params, cfg, num_slots=args.slots, max_len=max_len, layout="paged",
        page_size=args.page_size, total_pages=1 + args.slots * n_blocks,
        use_kernels=args.use_kernels, eos_id=args.eos_id,
        temperature=args.temperature, top_k=args.top_k,
        rng=jax.random.PRNGKey(args.seed + 1), obs=obs)
    eng.run(reqs)                      # warm the compile caches
    if obs is not None:
        obs.clear()                    # drop warmup spans/latencies
    t0 = time.time()
    comps = eng.run(reqs)
    useful = sum(len(c.tokens) for c in comps.values())
    cont = time.time() - t0
    stats = eng.stats()
    # static lockstep baseline: same trace, equal cache memory (slots x
    # max_len contiguous rows == the paged pool above)
    run_static_trace(params, cfg, reqs, batch=args.slots, max_len=max_len,
                     use_kernels=args.use_kernels)   # warm
    t0 = time.time()
    static_useful = run_static_trace(params, cfg, reqs, batch=args.slots,
                                     max_len=max_len,
                                     use_kernels=args.use_kernels)
    stat = time.time() - t0
    print(f"continuous: {useful} useful tok in {cont:.2f}s "
          f"({useful / cont:.1f} useful tok/s, "
          f"{stats['raw_tok_s']:.1f} raw tok/s, "
          f"{int(stats['dropped_tokens'])} dropped, "
          f"{eng.steps} decode steps)")
    print(f"static:     {static_useful} tok in {stat:.2f}s "
          f"({static_useful / stat:.1f} tok/s)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b-reduced")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--use-kernels", action="store_true",
                    help="fused flash prefill + flash-decode Pallas kernel")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; > 0 samples logits/temperature")
    ap.add_argument("--top-k", type=int, default=0,
                    help="restrict sampling to the top-k logits (0 = all)")
    ap.add_argument("--prompt-lens", default="",
                    help="comma-separated per-sequence prompt lengths "
                         "(<= --prompt-len); prompts are left-padded ragged")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching engine under a Poisson trace "
                         "(paged KV cache) vs the static baseline")
    ap.add_argument("--rate", type=float, default=0.5,
                    help="--continuous: arrivals per decode step")
    ap.add_argument("--requests", type=int, default=16,
                    help="--continuous: total requests in the trace")
    ap.add_argument("--slots", type=int, default=4,
                    help="--continuous: decode slots (= static batch)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="--continuous: KV cache page size (slots/page)")
    ap.add_argument("--max-len", type=int, default=0,
                    help="--continuous: cache depth (0 = 4x prompt-len)")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="--continuous: retire rows on this token id")
    ap.add_argument("--trace", default="",
                    help="write a Chrome/Perfetto span trace JSON here")
    ap.add_argument("--metrics-out", default="",
                    help="append the metrics registry as JSONL here")
    ap.add_argument("--device-trace", default="",
                    help="jax.profiler trace logdir (device activity "
                         "aligned under the host spans)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    obs = None
    if args.trace or args.metrics_out or args.device_trace:
        obs = Observability(annotate_device=bool(args.device_trace))
    cfg = dataclasses.replace(get_config(args.arch), dtype=args.dtype)
    rng = jax.random.PRNGKey(args.seed)
    params = T.init_params(rng, cfg)
    if args.continuous:
        if args.device_trace:
            with device_trace(args.device_trace):
                _run_continuous(params, cfg, args, obs=obs)
        else:
            _run_continuous(params, cfg, args, obs=obs)
        _write_obs(args, obs=obs)
        return
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    prompt_lens = None
    if args.prompt_lens:
        lens = [int(x) for x in args.prompt_lens.split(",")]
        if (len(lens) != args.batch or max(lens) > args.prompt_len
                or min(lens) < 1):
            raise SystemExit("--prompt-lens needs --batch entries, each in "
                             "[1, --prompt-len]")
        prompt_lens = jnp.array(lens, jnp.int32)
        # left-pad: real tokens right-aligned, pad id 0 on the left
        col = jnp.arange(args.prompt_len)[None]
        prompts = jnp.where(col >= args.prompt_len - prompt_lens[:, None],
                            prompts, 0)
    memory = None
    if cfg.vision is not None:
        memory = 0.1 * jax.random.normal(
            jax.random.PRNGKey(2),
            (args.batch, cfg.vision.n_image_tokens, cfg.d_model))
    if cfg.encoder is not None:
        frames = 0.1 * jax.random.normal(
            jax.random.PRNGKey(2), (args.batch, 32, cfg.encoder.d_model))
        memory = T.encode(params, cfg, frames.astype(jnp.dtype(cfg.dtype)))

    gen = jax.jit(lambda p, toks: generate(
        p, cfg, toks, max_new_tokens=args.max_new, memory=memory,
        use_kernels=args.use_kernels, temperature=args.temperature,
        top_k=args.top_k, rng=jax.random.PRNGKey(args.seed + 1),
        prompt_lens=prompt_lens))

    def run():
        return gen(params, prompts)

    span = (obs.tracer if obs is not None else NULL_TRACER).span
    n_new = args.batch * args.max_new
    t0 = time.time()
    with span("serve.generate_cold", batch=args.batch, max_new=args.max_new):
        out = run()
        out.block_until_ready()
    cold = time.time() - t0
    # explicit warmup: a fully-blocked steady-state call, so neither compile
    # nor async dispatch from the cold run can leak into the warm number
    jax.block_until_ready(run())
    t0 = time.time()
    with span("serve.generate_warm", batch=args.batch, max_new=args.max_new):
        out = run()
        out.block_until_ready()
    warm = time.time() - t0
    if obs is not None:
        obs.registry.observe("serve/generate_warm_s", warm)
        obs.registry.set("serve/generate_warm_tok_s", n_new / warm)
    print(f"generated {out.shape} kernels={args.use_kernels} "
          f"temperature={args.temperature}")
    print(f"cold: {cold:.2f}s ({n_new / cold:.1f} tok/s incl. compile)   "
          f"warm: {warm:.2f}s ({n_new / warm:.1f} tok/s)")
    print("sample row:", out[0, :32].tolist())
    _write_obs(args, obs=obs)


if __name__ == "__main__":
    main()
