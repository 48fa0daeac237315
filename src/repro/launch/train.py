"""Training launcher.

Two modes:
- host mode (default): runs a real training loop on the local device(s) —
  the end-to-end driver (examples/train_100m.py uses it to train a ~100M
  LM for a few hundred steps on synthetic data).
- mesh mode (--mesh single|multi): builds the production mesh and runs the
  same pjit train step the dry-run lowers (requires real hardware of that
  size; on this container use launch.dryrun instead).

Usage:
    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b-reduced \
        --steps 200 --batch 64 --seq-len 128 --lr-rule sqrt --ra
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save as ckpt_save
from repro.configs.registry import get_config
from repro.core import DiffusionTracker, LargeBatchConfig, Regime
from repro.data.synthetic import lm_sequences, token_lm
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import transformer as T
from repro.obs import Observability
from repro.obs.trace import NULL_TRACER
from repro.optim import sgd
from repro.sharding import rules
from repro.train.trainer import make_lm_train_step


def build_batches(cfg, *, batch: int, seq_len: int, n_tokens: int,
                  seed: int = 0):
    stream = token_lm(seed, vocab_size=cfg.vocab_size, n_tokens=n_tokens)
    seqs = lm_sequences(stream, seq_len)
    return seqs


def extra_inputs(cfg, batch: int, seq_len: int, rng) -> Dict[str, jax.Array]:
    out = {}
    # one independent subkey per synthetic modality: a config with both an
    # encoder and a vision tower must not draw the same latents twice
    r_frames, r_image = jax.random.split(rng)
    if cfg.encoder is not None:
        F = max(1, seq_len // cfg.encoder.frame_ratio)
        out["frames"] = 0.1 * jax.random.normal(
            r_frames, (batch, F, cfg.encoder.d_model), jnp.float32
        ).astype(jnp.dtype(cfg.dtype))
    if cfg.vision is not None:
        out["image_embeds"] = 0.1 * jax.random.normal(
            r_image, (batch, cfg.vision.n_image_tokens, cfg.d_model),
            jnp.float32
        ).astype(jnp.dtype(cfg.dtype))
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b-reduced")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--base-batch", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--base-lr", type=float, default=0.05)
    ap.add_argument("--lr-rule", default="sqrt",
                    choices=["sqrt", "linear", "none"])
    ap.add_argument("--ra", action="store_true", help="regime adaptation")
    ap.add_argument("--ghost-noise", type=float, default=0.0)
    ap.add_argument("--grad-clip", type=float, default=1.0)
    ap.add_argument("--mesh", default="host",
                    choices=["host", "single", "multi"])
    ap.add_argument("--parallel", default="pjit",
                    choices=["pjit", "shard_map"],
                    help="pjit: GSPMD auto-sharding from sharding/rules.py; "
                         "shard_map: the unified 2-D layer "
                         "(train/parallel.py) — batch over dp axes, expert "
                         "weights over 'model', explicit collectives")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--log-every", type=int, default=20)
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--trace", default="",
                    help="write a Chrome/Perfetto span trace JSON here")
    ap.add_argument("--metrics-out", default="",
                    help="append the metrics registry as JSONL here")
    args = ap.parse_args()
    enable_compile_cache()

    obs = (Observability() if (args.trace or args.metrics_out) else None)
    tracer = obs.tracer if obs is not None else NULL_TRACER
    reg = obs.registry if obs is not None else None

    cfg = get_config(args.arch)
    cfg = dataclasses.replace(cfg, dtype=args.dtype)
    lb = LargeBatchConfig(
        batch_size=args.batch, base_batch_size=args.base_batch,
        lr_rule=args.lr_rule, regime_adaptation=args.ra,
        grad_clip=args.grad_clip, ghost_noise=args.ghost_noise)
    small = Regime(base_lr=args.base_lr, total_steps=args.steps,
                   drop_every=max(1, args.steps // 3))
    regime = lb.build_regime(small)

    mesh = {"host": make_host_mesh,
            "single": lambda: make_production_mesh(multi_pod=False),
            "multi": lambda: make_production_mesh(multi_pod=True)}[args.mesh]()

    rng = jax.random.PRNGKey(0)
    params = T.init_params(rng, cfg)
    opt_state = sgd.init(params)
    if args.parallel == "shard_map":
        # unified 2-D layer: the shard_map carries its own mesh/specs — no
        # ambient mesh context, no pjit placement (the first step shards).
        step_fn = make_lm_train_step(cfg, lb, regime, mesh=mesh,
                                     params=params)
        mesh_ctx = contextlib.nullcontext()
    else:
        pshard = rules.param_shardings(params, mesh, cfg)
        params = jax.device_put(params, pshard)
        step_fn = make_lm_train_step(cfg, lb, regime)
        mesh_ctx = jax.set_mesh(mesh)
    with mesh_ctx:
        step_jit = jax.jit(step_fn, donate_argnums=(0, 1))

        seqs = build_batches(cfg, batch=args.batch, seq_len=args.seq_len,
                             n_tokens=args.batch * args.seq_len * 64)
        nprng = np.random.RandomState(1)
        tracker = DiffusionTracker(params)
        t0 = time.time()
        for step in range(regime.total_steps):
            idx = nprng.randint(0, seqs.shape[0], size=args.batch)
            batch = {"tokens": jnp.asarray(seqs[idx])}
            batch.update(extra_inputs(cfg, args.batch, args.seq_len,
                                      jax.random.fold_in(rng, 10_000 + step)))
            ts = time.perf_counter()
            with tracer.span("train.step", step=step, batch=args.batch):
                params, opt_state, metrics = step_jit(
                    params, opt_state, batch, jnp.int32(step),
                    jax.random.fold_in(rng, step))
                if reg is not None:
                    jax.block_until_ready(metrics["loss"])
            if reg is not None:
                reg.observe("train/step_time_s", time.perf_counter() - ts)
                reg.observe("train/loss", float(metrics["loss"]))
                reg.set("train/lr", float(metrics["lr"]))
                reg.set("train/batch_size", args.batch)
                if "grad_norm" in metrics:
                    reg.observe("train/grad_norm",
                                float(metrics["grad_norm"]))
                reg.inc("train/steps")
            if step % args.log_every == 0 or step == regime.total_steps - 1:
                d = tracker.record(step + 1, params)
                if reg is not None:
                    reg.observe("train/weight_dist", float(d))
                print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                      f"ce {float(metrics['ce']):.4f} "
                      f"lr {float(metrics['lr']):.4f} |w-w0| {d:.3f}",
                      flush=True)
        dt = time.time() - t0
        fit = tracker.log_fit(burn_in=2)
        print(f"done in {dt:.1f}s; log-diffusion fit slope="
              f"{fit['slope']:.3f} r2={fit['r2']:.3f}")
        if args.ckpt:
            ckpt_save(args.ckpt, regime.total_steps, params, opt_state,
                      extra={"arch": args.arch})
            print(f"checkpoint written to {args.ckpt}")
    if obs is not None:
        obs.write(args.trace, args.metrics_out)
        table = obs.summary()
        if table:
            print(table)


if __name__ == "__main__":
    main()
