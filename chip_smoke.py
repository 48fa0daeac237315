#!/usr/bin/env python3
"""Smoke test of the program's main path on one TPU.

One process drives three phases through the entry points a user calls:

* ``gbn_train``: the paper's workload. ``make_vision_train_step`` on
  ResNet44/CIFAR-10 at its published widths, with the Pallas ghost batch
  norm kernels, ghost batch 128 and the paper's large batch of 4096, for 5
  SGD steps on seeded synthetic 32x32x3 images.
* ``lm_train``: ``make_lm_train_step`` on qwen3-1.7b at its published
  widths in bf16, with the Pallas kernels and remat, 8 x 2048 tokens a
  step, for 4 steps (depth cut to what one chip holds; see ``LM_LAYERS``).
* ``serve``: ``ContinuousEngine`` on full-depth qwen3-1.7b with a paged
  bf16 KV cache and the Pallas kernels: 8 slots, 8 greedy requests with
  ragged prompts of 128-512 tokens and 32 new tokens each.

Each phase is checked against the same entry point with
``use_kernels=False`` on the chip, each compiled step must hold a Mosaic
kernel (``tpu_custom_call``), and a kernel that falls back to its jnp
oracle (``KernelFallbackWarning``) is an error. Data and weights come from
``--seed``.

``--chips 4`` runs only the two sharded paths, each beside its unsharded
twin: the GBN data-parallel step on a ``("data", 4)`` mesh, and the qwen3
TP+FSDP step on the ``(data=2, model=2)`` mesh.

Every line but the last starts with ``[chip run]``. The last line is one
JSON object naming the device. With no TPU attached, a failed phase or a
fallen-back kernel the script exits non-zero and prints no such line.

Usage::

    python chip_smoke.py [--seed 0]
    python chip_smoke.py --chips 4
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import os
import sys
import time
import traceback
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

GBN_BATCH = 4096            # the paper's large batch: fits one v5e whole
GHOST = 128
GBN_STEPS = 5
LM_ARCH = "qwen3-1.7b"
LM_ROWS, LM_SEQ = 8, 2048
LM_STEPS = 4
# The training phases cut qwen3-1.7b's 28 layers to 20: compiled for one
# v5e (15.75 GiB of HBM), the use_kernels=False reference step needs more
# than the chip holds from 22 layers up, and the kernel step at 28 (the
# f32 momentum alone is 7 GB at full depth).
LM_LAYERS = 20
CE_CHUNK = 6912             # streaming-CE vocab chunk: 152064 = 22 x 6912
SERVE_SLOTS = 8
PROMPT_LENS = (128, 300, 512, 128, 300, 512, 128, 300)
NEW_TOKENS = 32

# Tolerances of each comparison with the use_kernels=False twin.
# GBN: the model is f32; the kernel's one-pass variance (E[x^2] - mu^2 vs
# the oracle's two-pass) and its summation order differ ~1e-6 relative per
# statistic. The timed step (the TPU's default matmul precision: one bf16
# pass per f32 convolution) is held to its loss and global gradient norm.
# Per-leaf gradient norms are compared at f32 matmul precision: at the
# default, the gamma/beta gradients of the 16-channel layers, sums that
# cancel over 4M positions, differed by up to 3% between the two paths on
# a TPU v5e; in f32 on a CPU the same leaves differ by 1.2e-3 at most.
GBN_COMPARE_PRECISION = "highest"
GBN_LOSS_RTOL = 1e-3
GBN_GRAD_RTOL = 1e-2        # per-leaf gradient norms and the global norm
# The GBN kernels alone against the jnp oracle, f32 on both sides with no
# matmul anywhere: each output's normwise error is a few f32 roundings of
# tiled sums over up to 4M rows (~1e-6 on a CPU), so 1e-4 holds a sound
# kernel and fails one that loses part of a sum.
GBN_KERNEL_RTOL = 1e-4
# LM: bf16 activations; the fused kernels round at other points than the
# jnp composition (flash attention's online softmax, the fused SwiGLU and
# rmsnorm epilogues), ~4e-3 relative each, through every layer.
LM_LOSS_RTOL = 1e-2
LM_GRAD_RTOL = 5e-2
# Serving: greedy argmax over bf16 logits, where near ties are common: the
# two engines part at the first one. Every token of the kernel engine must
# be the argmax of the reference model (use_kernels=False, f32 logits,
# teacher-forced on the kernel engine's own tokens) within LOGIT_TOL: four
# bf16 ulps at the magnitude of the top logits (~4) of this randomly
# initialised model.
LOGIT_TOL = 0.125
# Sharded vs unsharded (4 chips): the same tolerances as above, per dtype.


class SmokeFailure(RuntimeError):
    """A check of the smoke test did not hold."""


def say(phase: str, **kv) -> None:
    print(f"[chip run] {phase}: "
          + ", ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


def require_kernel(phase: str, compiled) -> None:
    """The compiled program must hold a Mosaic kernel: a step that lost
    its kernels still runs, on the jnp path, and proves nothing."""
    if "tpu_custom_call" not in compiled.as_text():
        raise SmokeFailure(f"{phase}: no tpu_custom_call in the compiled "
                           f"step: its Pallas kernels did not run")


def agree(phase: str, what: str, got, want, rtol: float,
          atol: float = 0.0) -> None:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    err = np.abs(got - want)
    rel = float(np.max(err / np.maximum(np.abs(want), 1e-30)))
    ok = bool(np.all(np.isfinite(got))
              and np.all(err <= atol + rtol * np.abs(want)))
    say(phase, check=what, max_rel_err=rel, rtol=rtol, ok=ok)
    if not ok:
        raise SmokeFailure(f"{phase}: {what}: {got} vs {want} (rtol {rtol})")


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", -1)


def leaf_norms(tree):
    """Per-leaf L2 norms of a pytree, on the host."""
    return jax.device_get(jax.tree.leaves(jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))),
        tree)))


@dataclasses.dataclass
class Run:
    """What :func:`run_steps` observed."""
    compiled: object            # the compiled step
    compile_s: float
    metrics: list               # per step, on the host
    seconds: list               # per step, host clock, ends on the metrics
    grads: list                 # per-leaf norms of the first step's momentum
    final: object = None        # ``inspect(final state)``


def run_steps(phase: str, step_fn, state_fn, inputs_fn, steps: int, *,
              in_shardings=None, inspect=None) -> Run:
    """Compile ``step_fn`` ahead of time, build its state from
    ``state_fn`` (a tuple whose last entry is the optimizer state) and
    take ``steps`` steps. The first step's momentum is its clipped
    gradient; its per-leaf norms are what the twins compare. The state is
    dropped on return, so the next run has the device to itself."""
    shapes = jax.eval_shape(state_fn)
    n = len(shapes)
    kw = {} if in_shardings is None else {"in_shardings": in_shardings}
    jitted = jax.jit(step_fn, donate_argnums=tuple(range(n)), **kw)

    def inputs(i):
        args = inputs_fn(i)
        if in_shardings is not None:
            args = jax.device_put(args, tuple(in_shardings[n:]))
        return jax.block_until_ready(args)

    t0 = time.perf_counter()
    compiled = jitted.lower(*shapes, *inputs(0)).compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    if mem is not None:
        say(phase, compiled_argument_bytes=mem.argument_size_in_bytes,
            compiled_temp_bytes=mem.temp_size_in_bytes)
    state = jax.jit(state_fn, out_shardings=(
        None if in_shardings is None else tuple(in_shardings[:n])))()
    run = Run(compiled, compile_s, [], [], [])
    for i in range(steps):
        args = inputs(i)
        t0 = time.perf_counter()
        *state, m = compiled(*state, *args)
        run.metrics.append(jax.device_get(m))   # waits for the step
        run.seconds.append(time.perf_counter() - t0)
        if i == 0:
            run.grads = leaf_norms(state[-1].momentum)
    if inspect is not None:
        run.final = inspect(state)
    return run


def report(phase: str, run: Run, per_step: int, unit: str) -> None:
    warm = run.seconds[1:] or run.seconds
    warm_s = sum(warm) / len(warm)
    losses = [float(m["loss"]) for m in run.metrics]
    say(phase, compile_s=run.compile_s, warm_step_s=warm_s,
        **{f"{unit}_per_s": per_step / warm_s}, losses=losses,
        grad_norms=[float(m["grad_norm"]) for m in run.metrics],
        peak_bytes_in_use=peak_bytes())
    if not np.all(np.isfinite(losses)):
        raise SmokeFailure(f"{phase}: non-finite losses {losses}")


def compare_steps(phase: str, got: Run, want: Run, loss_rtol: float,
                  grad_rtol: float, per_leaf: bool = True) -> None:
    g, w = got.metrics[0], want.metrics[0]
    agree(phase, "first-step loss", g["loss"], w["loss"], loss_rtol)
    agree(phase, "first-step grad_norm", g["grad_norm"], w["grad_norm"],
          grad_rtol)
    if per_leaf:
        agree(phase, "first-step per-leaf gradient norms", got.grads,
              want.grads, grad_rtol, atol=1e-6)


# ---------------------------------------------------------------------------
# phase gbn_train: the paper's large-batch GBN training
# ---------------------------------------------------------------------------


def _vision_setup(seed: int, cfg, batch: int):
    from repro.core import Regime, presets
    from repro.models.cnn import model_fns
    from repro.optim import sgd

    init_fn, apply_fn = model_fns(cfg)
    key = jax.random.PRNGKey(seed)
    lb = presets(batch, ghost=GHOST)["LB+LR+GBN+RA"]
    regime = lb.build_regime(Regime(base_lr=0.1, total_steps=1000,
                                    drop_every=400))

    def state():
        params, bn = init_fn(jax.random.fold_in(key, 0), cfg)
        return params, bn, sgd.init(params)

    def inputs(i):
        k = jax.random.fold_in(key, 100 + i)
        x = jax.random.normal(k, (batch,) + cfg.input_shape)
        y = jax.random.randint(jax.random.fold_in(k, 1), (batch,), 0,
                               cfg.n_classes)
        return x, y, jnp.int32(i), jax.random.fold_in(key, 200 + i)

    return apply_fn, lb, regime, state, inputs


def gbn_train(seed: int, cfg=None, batch: int = GBN_BATCH,
              steps: int = GBN_STEPS) -> None:
    from repro.configs.paper_models import RESNET44_CIFAR10
    from repro.train.trainer import make_vision_train_step
    cfg = cfg or RESNET44_CIFAR10
    phase = "gbn_train"
    apply_fn, lb, regime, state, inputs = _vision_setup(seed, cfg, batch)
    say(phase, model=cfg.name, batch=batch, ghost_batch=lb.ghost_batch_size,
        images=f"{cfg.input_shape} f32, seeded synthetic")

    def step(uk):
        return make_vision_train_step(apply_fn, cfg, lb, regime,
                                      use_kernels=uk)

    run = run_steps(phase, step(True), state, inputs, steps)
    require_kernel(phase, run.compiled)
    report(phase, run, batch, "images")
    ref = run_steps(phase + "[use_kernels=False]", step(False), state,
                    inputs, 1)
    say(phase, reference_compile_s=ref.compile_s)
    compare_steps(phase, run, ref, GBN_LOSS_RTOL, GBN_GRAD_RTOL,
                  per_leaf=False)
    del ref
    tag = f"[matmul precision {GBN_COMPARE_PRECISION}]"
    with jax.default_matmul_precision(GBN_COMPARE_PRECISION):
        got = run_steps(phase + tag, step(True), state, inputs, 1)
        ref = run_steps(phase + "[use_kernels=False]" + tag, step(False),
                        state, inputs, 1)
    require_kernel(phase + tag, got.compiled)
    say(phase, compare_compile_s=got.compile_s + ref.compile_s)
    compare_steps(phase + tag, got, ref, GBN_LOSS_RTOL, GBN_GRAD_RTOL)
    del got, ref
    h, w = cfg.input_shape[:2]
    gbn_kernels(phase, seed, [(batch // GHOST, GHOST * (h >> i) * (w >> i), c)
                              for i, c in enumerate(cfg.channels)])


def gbn_kernels(phase: str, seed: int, shapes) -> None:
    """The GBN forward and backward kernels alone, against ``jax.vjp`` of
    the jnp oracle at each (ghosts, rows, channels) shape, in f32. Neither
    side has a convolution or a matmul, so a disagreement is the
    kernels' own."""
    from repro.kernels import ops, ref
    key = jax.random.PRNGKey(seed)
    for G, R, C in shapes:
        k = jax.random.split(jax.random.fold_in(key, 300 + C), 6)
        x = 0.5 + jax.random.normal(k[0], (G, R, C))
        gamma = 1.0 + 0.1 * jax.random.normal(k[1], (C,))
        beta = 0.1 * jax.random.normal(k[2], (C,))
        cts = (jax.random.normal(k[3], (G, R, C)),
               jax.random.normal(k[4], (G, C)),
               jax.random.normal(k[5], (G, C)))

        def fwd_bwd(fn, x, gamma, beta, cts):
            out, vjp = jax.vjp(fn, x, gamma, beta)
            return out + vjp(cts)

        args = (x, gamma, beta, cts)
        kern = jax.jit(functools.partial(fwd_bwd, ops.gbn_forward)).lower(
            *args).compile()
        require_kernel(phase, kern)
        got = kern(*args)
        want = jax.jit(functools.partial(fwd_bwd, ref.gbn_ref))(*args)
        names = ("y", "mu", "var", "dx", "dgamma", "dbeta")
        err = {n: float(jnp.linalg.norm((a - b).ravel())
                        / jnp.linalg.norm(b.ravel()))
               for n, a, b in zip(names, got, want)}
        ok = all(e <= GBN_KERNEL_RTOL for e in err.values())
        say(phase, check=f"GBN kernels vs jnp oracle at {(G, R, C)} f32",
            **{f"{n}_rel_err": e for n, e in err.items()},
            rtol=GBN_KERNEL_RTOL, ok=ok)
        if not ok:
            raise SmokeFailure(f"{phase}: GBN kernels at {(G, R, C)}: {err}")


# ---------------------------------------------------------------------------
# phase lm_train: qwen3 LM training with the Pallas kernels
# ---------------------------------------------------------------------------


def _lm_setup(seed: int, cfg, rows: int, seq: int):
    from repro.core import LargeBatchConfig, Regime
    from repro.models import transformer as T
    from repro.optim import sgd

    key = jax.random.PRNGKey(seed)
    lb = LargeBatchConfig(batch_size=rows, base_batch_size=rows)
    regime = Regime(base_lr=0.01, total_steps=1000, drop_every=400)

    def state():
        params = T.init_params(jax.random.fold_in(key, 0), cfg)
        return params, sgd.init(params)

    def inputs(i):
        toks = jax.random.randint(jax.random.fold_in(key, 100 + i),
                                  (rows, seq), 0, cfg.vocab_size)
        return ({"tokens": toks}, jnp.int32(i),
                jax.random.fold_in(key, 200 + i))

    return lb, regime, state, inputs


def lm_train(seed: int, cfg=None, rows: int = LM_ROWS, seq: int = LM_SEQ,
             steps: int = LM_STEPS, ce_chunk: int = CE_CHUNK) -> None:
    from repro.configs.registry import get_config
    from repro.train.trainer import make_lm_train_step
    phase = "lm_train"
    full = get_config(LM_ARCH)
    cfg = cfg or dataclasses.replace(full, body_repeats=LM_LAYERS)
    lb, regime, state, inputs = _lm_setup(seed, cfg, rows, seq)
    say(phase, model=cfg.name, layers=f"{cfg.n_layers} of {full.n_layers}",
        tokens_per_step=f"{rows}x{seq}", dtype=cfg.dtype,
        depth_cut="the use_kernels=False reference step does not fit one "
                  "v5e from 22 layers up" if cfg.n_layers < full.n_layers
        else "none")

    def step(uk):
        return make_lm_train_step(cfg, lb, regime, use_kernels=uk,
                                  remat=True, ce_chunk=ce_chunk)

    run = run_steps(phase, step(True), state, inputs, steps)
    require_kernel(phase, run.compiled)
    report(phase, run, rows * seq, "tokens")
    ref = run_steps(phase + "[use_kernels=False]", step(False), state,
                    inputs, 1)
    say(phase, reference_compile_s=ref.compile_s)
    compare_steps(phase, run, ref, LM_LOSS_RTOL, LM_GRAD_RTOL)


# ---------------------------------------------------------------------------
# phase serve: the continuous engine, paged cache, greedy decode
# ---------------------------------------------------------------------------


def _reference_margins(params, cfg, requests, completions):
    """Teacher-force each request's prompt + generated tokens through the
    plain (use_kernels=False) model; return, per request, the f32 logit
    margin max(logits) - logits[token] at every generated position."""
    from repro.models import transformer as T
    from repro.serving.engine import mask_padded_vocab

    width = max(len(r.prompt) + r.max_new_tokens for r in requests)
    new = max(r.max_new_tokens for r in requests)
    seqs = np.zeros((len(requests), width), np.int32)
    where = np.zeros((len(requests), new), np.int32)
    toks = np.zeros((len(requests), new), np.int32)
    for i, r in enumerate(requests):
        gen = completions[r.id].tokens
        L = len(r.prompt)
        seqs[i, :L] = r.prompt
        seqs[i, L:L + len(gen)] = gen
        where[i, :len(gen)] = L - 1 + np.arange(len(gen))
        toks[i, :len(gen)] = gen

    @jax.jit
    def margins(params, seqs, where, toks):
        x, _ = T.hidden_states(params, cfg, seqs)          # causal: the
        x = jnp.take_along_axis(x, where[..., None], 1)    # right pad is
        head = params["embed"] if cfg.tie_embeddings else params["head"]
        lg = mask_padded_vocab(cfg, x.astype(jnp.float32)  # never seen
                               @ head.astype(jnp.float32).T)
        pick = jnp.take_along_axis(lg, toks[..., None], -1)[..., 0]
        return lg.max(-1) - pick

    return np.asarray(margins(params, seqs, where, toks))


def serve(seed: int, cfg=None, prompt_lens=PROMPT_LENS,
          new_tokens: int = NEW_TOKENS, slots: int = SERVE_SLOTS) -> None:
    from repro.configs.registry import get_config
    from repro.models import transformer as T
    from repro.serving import ContinuousEngine
    from repro.serving.engine import Request, make_serve_step
    phase = "serve"
    cfg = cfg or get_config(LM_ARCH)
    params = jax.jit(lambda: T.init_params(jax.random.PRNGKey(seed), cfg))()
    rng = np.random.default_rng(seed)
    requests = [Request(id=i, prompt=rng.integers(0, cfg.vocab_size, L,
                                                  dtype=np.int32),
                        max_new_tokens=new_tokens)
                for i, L in enumerate(prompt_lens)]
    max_len = -(-(max(prompt_lens) + new_tokens) // 16) * 16
    say(phase, model=cfg.name, layers=cfg.n_layers, slots=slots,
        requests=len(requests), prompt_lens=list(prompt_lens),
        new_tokens=new_tokens, layout="paged", cache_dtype=cfg.dtype)

    def engine(uk):
        return ContinuousEngine(params, cfg, num_slots=slots,
                                max_len=max_len, layout="paged",
                                use_kernels=uk)

    eng = engine(True)
    t0 = time.perf_counter()
    eng.run(requests)                          # compiles on the way
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    got = eng.run(requests)                    # warm: same shapes
    warm_s = time.perf_counter() - t0
    stats = eng.stats()
    decode = jax.jit(make_serve_step(cfg, True), donate_argnums=(1,)).lower(
        params, eng.cache, jnp.zeros((slots, 1), jnp.int32),
        jnp.zeros((slots,), jnp.int32)).compile()
    require_kernel(phase, decode)
    del eng, decode
    say(phase, cold_run_s=cold_s, warm_run_s=warm_s,
        decode_steps=int(stats["steps"]),
        useful_tokens=int(stats["useful_tokens"]),
        useful_tok_s=stats["useful_tok_s"], peak_bytes_in_use=peak_bytes())

    # the twins agree token for token until a near tie sends them apart;
    # every kernel-engine token, the one at the split included, must be
    # the reference model's argmax within LOGIT_TOL
    want = engine(False).run(requests)
    if any(len(got[r.id].tokens) != new_tokens for r in requests):
        raise SmokeFailure(f"{phase}: a request did not get {new_tokens} "
                           f"tokens")
    equal = sum(next((t for t, (a, b) in enumerate(zip(got[r.id].tokens,
                                                       want[r.id].tokens))
                      if a != b), new_tokens) for r in requests)
    worst = float(_reference_margins(params, cfg, requests, got).max())
    say(phase, tokens_before_first_divergence=f"{equal}/"
        f"{len(requests) * new_tokens}",
        max_teacher_forced_margin=worst, logit_tol=LOGIT_TOL,
        ok=worst <= LOGIT_TOL)
    if worst > LOGIT_TOL:
        raise SmokeFailure(f"{phase}: a kernel-engine token trails the "
                           f"reference argmax by {worst:.4f} > {LOGIT_TOL}")


# ---------------------------------------------------------------------------
# --chips 4: the two sharded paths beside their unsharded twins
# ---------------------------------------------------------------------------


def _layout(arr) -> dict:
    shards = arr.addressable_shards
    return {"devices": {s.device for s in shards},
            "shard_shape": tuple(shards[0].data.shape),
            "global_shape": tuple(arr.shape),
            "spec": str(getattr(arr.sharding, "spec", arr.sharding))}


def _on_all(phase: str, what: str, lay: dict, sharded: bool) -> None:
    """``lay`` (from :func:`_layout`) must span every device and, for a
    sharded leaf, hold less than the whole array on each."""
    ok = lay["devices"] == set(jax.devices()) and (
        not sharded or lay["shard_shape"] != lay["global_shape"])
    say(phase, check=f"{what} on all {len(jax.devices())} devices",
        spec=lay["spec"], shard_shape=lay["shard_shape"],
        global_shape=lay["global_shape"], ok=ok)
    if not ok:
        raise SmokeFailure(f"{phase}: {what} is not laid out over all "
                           f"devices: {lay}")


def gbn_dp(seed: int, cfg=None, batch: int = GBN_BATCH,
           steps: int = 2) -> None:
    from repro.configs.paper_models import RESNET44_CIFAR10
    from repro.launch.mesh import DATA_AXIS, make_data_mesh
    from repro.train.data_parallel import make_dp_vision_train_step
    from repro.train.trainer import make_vision_train_step
    cfg = cfg or RESNET44_CIFAR10
    phase = "gbn_dp"
    apply_fn, lb, regime, state, inputs = _vision_setup(seed, cfg, batch)
    mesh = make_data_mesh()
    say(phase, model=cfg.name, global_batch=batch, mesh=dict(mesh.shape),
        ghost_batch=lb.ghost_batch_size)
    rep = NamedSharding(mesh, P())
    data = NamedSharding(mesh, P(DATA_AXIS))

    def twins(tag: str, n: int):
        one = run_steps(phase + "[one device]" + tag,
                        make_vision_train_step(apply_fn, cfg, lb, regime,
                                               use_kernels=True),
                        state, inputs, n)
        run = run_steps(phase + tag, make_dp_vision_train_step(
                            apply_fn, cfg, lb, regime, mesh,
                            use_kernels=True),
                        state, inputs, n,
                        in_shardings=(rep, rep, rep, data, data, rep, rep),
                        inspect=lambda st: _layout(
                            jax.tree.leaves(st[0])[0]))
        require_kernel(phase + tag, run.compiled)
        return one, run

    # the step users run, at the default matmul precision
    one, run = twins("", steps)
    report(phase, run, batch, "images")
    _on_all(phase, "a replicated weight", run.final, sharded=False)
    compare_steps(phase, run, one, GBN_LOSS_RTOL, GBN_GRAD_RTOL,
                  per_leaf=False)
    agree(phase, f"step-{steps} loss", run.metrics[-1]["loss"],
          one.metrics[-1]["loss"], GBN_LOSS_RTOL)
    del one, run
    # per-leaf gradients at f32 matmul precision (see GBN_COMPARE_PRECISION)
    tag = f"[matmul precision {GBN_COMPARE_PRECISION}]"
    with jax.default_matmul_precision(GBN_COMPARE_PRECISION):
        one, run = twins(tag, 1)
    compare_steps(phase + tag, run, one, GBN_LOSS_RTOL, GBN_GRAD_RTOL)


def lm_tp_fsdp(seed: int, cfg=None, rows: int = LM_ROWS, seq: int = LM_SEQ,
               steps: int = 2, ce_chunk: int = CE_CHUNK) -> None:
    from repro.configs.registry import get_config
    from repro.launch.mesh import DATA_AXIS, make_2d_mesh
    from repro.optim import sgd
    from repro.sharding import rules
    from repro.train import parallel as PAR
    from repro.train.trainer import make_lm_train_step
    phase = "lm_tp_fsdp"
    cfg = cfg or dataclasses.replace(get_config(LM_ARCH),
                                     body_repeats=LM_LAYERS)
    lb, regime, state, inputs = _lm_setup(seed, cfg, rows, seq)
    mesh = make_2d_mesh(model=2)
    say(phase, model=cfg.name, layers=cfg.n_layers, mesh=dict(mesh.shape),
        tokens_per_step=f"{rows}x{seq}")
    kw = dict(use_kernels=True, remat=True, ce_chunk=ce_chunk)
    one = run_steps(phase + "[one device]",
                    make_lm_train_step(cfg, lb, regime, **kw),
                    state, inputs, steps)
    params = jax.eval_shape(state)[0]
    psh = rules.to_shardings(PAR.mesh_param_specs(params, mesh, cfg=cfg,
                                                  tp=True, fsdp=True), mesh)
    rep = NamedSharding(mesh, P())
    data = NamedSharding(mesh, P(DATA_AXIS))

    def wq(st):
        return (_layout(st[0]["stack"]["body"][0]["mixer"]["wq"]),
                _layout(st[1].momentum["stack"]["body"][0]["mixer"]["wq"]))

    run = run_steps(phase, make_lm_train_step(cfg, lb, regime, mesh=mesh,
                                              params=params, tp=True,
                                              fsdp=True, **kw),
                    state, inputs, steps,
                    in_shardings=(psh, sgd.SGDState(momentum=psh, step=rep),
                                  {"tokens": data}, rep, rep),
                    inspect=wq)
    require_kernel(phase, run.compiled)
    report(phase, run, rows * seq, "tokens")
    _on_all(phase, "wq", run.final[0], sharded=True)
    _on_all(phase, "wq momentum", run.final[1], sharded=True)
    compare_steps(phase, run, one, LM_LOSS_RTOL, LM_GRAD_RTOL)
    agree(phase, f"step-{steps} loss", run.metrics[-1]["loss"],
          one.metrics[-1]["loss"], LM_LOSS_RTOL)


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded paths, on four chips")
    args = ap.parse_args(argv)
    try:
        from repro.kernels.ops import KernelFallbackWarning
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is not beside this "
              f"script ({e})", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU attached (JAX found {dev.platform}); "
              f"a smoke test off the chip proves nothing", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    warnings.simplefilter("error", KernelFallbackWarning)
    say("device", platform=dev.platform, kind=dev.device_kind,
        count=len(devices), seed=args.seed, compile_cache=cache_dir)
    phases = ((gbn_dp, lm_tp_fsdp) if args.chips == 4
              else (gbn_train, lm_train, serve))
    failed = []
    for phase in phases:
        t0 = time.perf_counter()
        try:
            phase(args.seed)
        except Exception:                      # report, run the rest
            traceback.print_exc()
            failed.append(phase.__name__)
        gc.collect()
        say(phase.__name__, phase_s=time.perf_counter() - t0,
            ok=phase.__name__ not in failed)
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
