"""Training loops wiring the large-batch toolkit into both model classes.

- ``make_lm_train_step``: next-token LM training for the assigned
  architectures (momentum SGD + clipping + noise + regime LR). The returned
  step is pjit-compatible: (params, opt_state, batch, step, rng) ->
  (params, opt_state, metrics).
- ``make_vision_train_step`` / ``train_vision``: the paper's Table-1 style
  experiments — models with (ghost) BN running state, SB/LB/+LR/+GBN/+RA
  presets, weight-distance (diffusion) tracking.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.configs.paper_models import VisionModelConfig
from repro.core.diffusion import DiffusionTracker
from repro.core.large_batch import LargeBatchConfig
from repro.core.metrics import MetricsLogger
from repro.core.regime import Regime
from repro.models import transformer as T
from repro.obs.trace import NULL_TRACER
from repro.optim import adam, sgd

Params = Any


def _obs_step_metrics(reg, t0: float, m: Dict[str, jax.Array],
                      batch_size: int) -> None:
    """Per-step training telemetry: step wall time (the caller blocked on
    the step's output first), grad norm, and the current schedule state
    (LR / batch size) — the signals the paper's measurement rests on.

    The metrics dict crosses to the host ONCE (``jax.device_get`` of the
    whole pytree); per-metric ``float(...)`` reads used to force a
    separate device sync each (lint rule ``host-sync``)."""
    reg.observe("train/step_time_s", time.perf_counter() - t0)
    m = jax.device_get(m)
    reg.set("train/lr", float(m["lr"]))
    reg.set("train/batch_size", batch_size)
    if "grad_norm" in m:
        reg.observe("train/grad_norm", float(m["grad_norm"]))
    reg.inc("train/steps")


# ---------------------------------------------------------------------------
# LM training (assigned architectures)
# ---------------------------------------------------------------------------


def make_lm_train_step(cfg: ModelConfig, lb: LargeBatchConfig,
                       regime: Regime, *, weight_decay: float = 0.0,
                       use_kernels: bool = False,
                       momentum_dtype: str = "float32",
                       remat: bool = False,
                       seq_parallel: bool = False,
                       ce_chunk: int = 0,
                       mesh=None, params: Optional[Params] = None,
                       tp: bool = False, fsdp: bool = False,
                       optimizer: str = "sgd") -> Callable:
    """Build the jit-able LM train step implementing the paper's recipe.

    ``use_kernels=True`` routes both LM mixers through the Pallas kernels —
    flash attention and the Mamba chunk scan — which are fully trainable:
    each pairs its forward with a dedicated Pallas backward kernel via
    ``jax.custom_vjp`` (see docs/kernels.md), so ``jax.value_and_grad`` here
    never differentiates through an interpreted kernel body or replays an
    oracle forward.

    With ``mesh`` (any mesh from :mod:`repro.launch.mesh`) the step runs
    sharded pod? x data x model through the unified parallelism layer
    (:mod:`repro.train.parallel`): batch over the dp axes, MoE expert
    weights over ``"model"``, plus ``tp=True`` (Megatron attention/MLP
    over "model") and ``fsdp=True`` (params + optimizer moments over the dp
    axes) — see :func:`repro.train.parallel.make_mesh_lm_train_step`.
    ``params`` (the parameter pytree or its shapes) is required then — the
    shard_map specs are derived from it. ``optimizer`` picks "sgd"
    (the paper's recipe) or "adam" (its adaptive baseline) on either path.
    """
    if mesh is not None:
        if params is None:
            raise ValueError("mesh-sharded LM step needs the params "
                             "pytree to derive its specs")
        from repro.train.parallel import make_mesh_lm_train_step
        return make_mesh_lm_train_step(
            cfg, lb, regime, mesh, params, weight_decay=weight_decay,
            use_kernels=use_kernels, momentum_dtype=momentum_dtype,
            remat=remat, seq_parallel=seq_parallel, ce_chunk=ce_chunk,
            tp=tp, fsdp=fsdp, optimizer=optimizer)
    if tp or fsdp:
        raise ValueError("tp/fsdp need a mesh")
    if optimizer not in ("sgd", "adam"):
        raise ValueError(f"unknown optimizer {optimizer!r}")
    sigma = lb.effective_noise_sigma()

    def train_step(params: Params, opt_state, batch: Dict[str, jax.Array],
                   step: jax.Array, rng: jax.Array):
        def loss_fn(p):
            return T.lm_loss(p, cfg, batch, use_kernels=use_kernels,
                             remat=remat, seq_parallel=seq_parallel,
                             ce_chunk=ce_chunk)

        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        lr = regime.lr_at(step)
        if optimizer == "adam":
            params2, opt_state2, opt_metrics = adam.update(
                grads, opt_state, params, lr=lr,
                weight_decay=weight_decay, grad_clip=lb.grad_clip)
        else:
            params2, opt_state2, opt_metrics = sgd.update(
                grads, opt_state, params,
                lr=lr, momentum=lb.momentum, nesterov=lb.nesterov,
                weight_decay=weight_decay, grad_clip=lb.grad_clip,
                noise_sigma=sigma, rng=rng, momentum_dtype=momentum_dtype)
        metrics = {"loss": loss, "lr": lr, **metrics, **opt_metrics}
        return params2, opt_state2, metrics

    return train_step


def make_lm_eval_step(cfg: ModelConfig, use_kernels: bool = False) -> Callable:
    def eval_step(params: Params, batch: Dict[str, jax.Array]):
        loss, metrics = T.lm_loss(params, cfg, batch,
                                  use_kernels=use_kernels)
        return metrics["ce"]

    return eval_step


# ---------------------------------------------------------------------------
# Vision training (the paper's own experiments)
# ---------------------------------------------------------------------------


def make_vision_loss_fn(model_apply: Callable, cfg: VisionModelConfig,
                        lb: LargeBatchConfig, *,
                        use_kernels: bool = False) -> Callable:
    """(params, bn_state, x, y) -> (nll, (new_bn_state, acc)).

    Shared by the single-device step below and the shard_map data-parallel
    step (:mod:`repro.train.data_parallel`) — in the sharded case it runs on
    each device's LOCAL batch, so the ghost-batch statistics inside
    ``model_apply`` are per-device by construction. Fully differentiable
    through the ``use_kernels=True`` GBN path (Pallas backward kernel via
    ``jax.custom_vjp``).
    """

    def loss_fn(p: Params, bn_state: Params, x: jax.Array, y: jax.Array):
        logits, new_state = model_apply(
            p, bn_state, cfg, x, training=True,
            ghost_batch_size=lb.ghost_batch_size,
            use_gbn=lb.use_gbn, use_kernels=use_kernels)
        with jax.named_scope("ce"):
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            nll = -jnp.take_along_axis(logp, y[:, None], axis=1).mean()
            acc = (logits.argmax(-1) == y).mean()
        return nll, (new_state, acc)

    return loss_fn


def make_vision_train_step(model_apply: Callable, cfg: VisionModelConfig,
                           lb: LargeBatchConfig, regime: Regime,
                           *, weight_decay: float = 5e-4,
                           use_kernels: bool = False) -> Callable:
    """Vision train step with GBN state threading.

    ``lb.use_gbn`` selects ghost vs full-batch statistics;
    ``lb.ghost_batch_size`` is Alg. 1's |B_S|.
    """
    sigma = lb.effective_noise_sigma()
    loss_fn = make_vision_loss_fn(model_apply, cfg, lb,
                                  use_kernels=use_kernels)

    def train_step(params: Params, bn_state: Params, opt_state: sgd.SGDState,
                   x: jax.Array, y: jax.Array, step: jax.Array,
                   rng: jax.Array):
        (loss, (new_state, acc)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, bn_state, x, y)
        lr = regime.lr_at(step)
        params2, opt_state2, m = sgd.update(
            grads, opt_state, params, lr=lr, momentum=lb.momentum,
            weight_decay=weight_decay, grad_clip=lb.grad_clip,
            noise_sigma=sigma, rng=rng)
        return params2, new_state, opt_state2, {
            "loss": loss, "acc": acc, "lr": lr, **m}

    return train_step


def make_vision_eval(model_apply: Callable, cfg: VisionModelConfig
                     ) -> Callable:
    @jax.jit
    def eval_batch(params, bn_state, x, y):
        logits, _ = model_apply(params, bn_state, cfg, x, training=False)
        return (logits.argmax(-1) == y).sum()

    def evaluate(params, bn_state, x, y, batch: int = 512) -> float:
        correct = 0
        for i in range(0, x.shape[0], batch):
            correct += int(eval_batch(params, bn_state,
                                      x[i:i + batch], y[i:i + batch]))
        return correct / x.shape[0]

    return evaluate


def _epoch_perm(shuffle_key: jax.Array, epoch: int, n: int) -> np.ndarray:
    """Deterministic per-epoch shuffle: a pure function of (key, epoch), so
    a run resumed at any (epoch, cursor) sees the same batch sequence as an
    uninterrupted one."""
    return np.asarray(
        jax.random.permutation(jax.random.fold_in(shuffle_key, epoch), n))


def _record_diffusion(step: int, total_steps: int, every: int) -> bool:
    if every > 0:
        return step % every == 0
    # auto cadence: dense early (the log-t regime), sparse after
    return step < 32 or step % max(1, total_steps // 64) == 0


def _save_run_state(checkpoint_dir: str, step: int, params, bn_state,
                    opt_state, *, epoch: int, cursor: int,
                    logger, tracker) -> None:
    from repro import checkpoint as ckpt
    extra: Dict[str, Any] = {"epoch": epoch, "cursor": cursor,
                             "metrics": logger.to_json()}
    if tracker is not None:
        extra["tracker"] = {"steps": list(tracker.steps),
                            "distances": list(tracker.distances)}
    # under a multi-process runtime each host writes only its addressable
    # shards (no gather); single-process keeps the consolidated layout
    ckpt.save(checkpoint_dir, step, params, opt_state, extra=extra,
              bn_state=bn_state, sharded=jax.process_count() > 1)


def _restore_run_state(checkpoint_dir, params, opt_state, bn_state, tracker):
    """Shared resume path: restore trees + (step, epoch, cursor, logger)
    from the latest checkpoint, or the fresh-run defaults when none exists.
    ``bn_state=None`` (the LM loop) skips the BN-state tree."""
    from repro import checkpoint as ckpt
    if not checkpoint_dir or ckpt.latest_step(checkpoint_dir) is None:
        return params, opt_state, bn_state, 0, 0, 0, MetricsLogger()
    params, _ = ckpt.restore(checkpoint_dir, params)
    opt_state, _ = ckpt.restore(checkpoint_dir, opt_state, kind="opt")
    if bn_state is not None:
        bn_state, _ = ckpt.restore(checkpoint_dir, bn_state, kind="state")
    meta = ckpt.load_meta(checkpoint_dir)
    logger = MetricsLogger.from_json(meta["metrics"])
    if tracker is not None and "tracker" in meta:
        tracker.load(meta["tracker"]["steps"], meta["tracker"]["distances"])
    return (params, opt_state, bn_state, meta["step"], meta["epoch"],
            meta["cursor"], logger)


def train_vision(model_fns, cfg: VisionModelConfig, data,
                 lb: LargeBatchConfig, regime: Regime, *, seed: int = 0,
                 eval_every: int = 0, track_diffusion: bool = True,
                 diffusion_every: int = 0,
                 log_fn: Optional[Callable[[str], None]] = None,
                 use_kernels: bool = False, mesh=None,
                 weight_decay: float = 5e-4,
                 batch_schedule=None,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 0,
                 resume: bool = True, obs=None) -> Dict[str, Any]:
    """Full training run; returns final/best accuracy + diffusion trace.

    With ``mesh`` (any mesh from :mod:`repro.launch.mesh` — the 1-D
    ``("data",)`` mesh or the 2-D ``(data, model)`` production shape) the
    step runs sharded data-parallel over the mesh's dp axes: each dp shard
    normalizes with its own ghost-batch statistics and only gradients cross
    devices.

    The PRNG is split three ways — init / per-step gradient noise / data
    shuffling — so no consumer reuses another's key. Shuffling is a pure
    function of (seed, epoch), which together with ``checkpoint_dir`` +
    ``checkpoint_every`` makes runs resumable: an interrupted run restarts
    from the last saved (params, bn_state, opt_state, epoch, cursor,
    metrics) and replays the identical batch sequence.

    ``batch_schedule`` (a :class:`repro.core.regime.BatchSchedule`) grows
    the batch size during training instead of decaying the LR (Smith et
    al. 2018); distinct batch sizes re-jit once each.

    ``metrics`` output: the returned dict carries a
    :class:`repro.core.metrics.MetricsLogger` under ``"metrics"``
    (the legacy ``"history"`` dict is derived from it).

    ``obs`` (a :class:`repro.obs.Observability`) wraps every step in a
    ``train.step`` span and emits the training telemetry set —
    ``train/step_time_s`` / ``train/grad_norm`` histograms, ``train/lr``
    and ``train/batch_size`` gauges, and the logger's series (eval
    accuracy, weight distance) mirrored under ``train/``. With ``obs``
    the loop blocks on each step's output to make the step time real;
    without it nothing is added to the dispatch path.
    """
    init_fn, apply_fn = model_fns
    init_key, noise_key, shuffle_key = jax.random.split(
        jax.random.PRNGKey(seed), 3)
    params, bn_state = init_fn(init_key, cfg)
    opt_state = sgd.init(params)
    tracker = DiffusionTracker(params) if track_diffusion else None
    params, opt_state, bn_state, step, epoch, cursor, logger = \
        _restore_run_state(checkpoint_dir if resume else None,
                           params, opt_state, bn_state, tracker)
    tracer = obs.tracer if obs is not None else NULL_TRACER
    reg = obs.registry if obs is not None else None
    if obs is not None:
        logger.attach_registry(obs.registry, prefix="train/")

    if mesh is not None:
        from repro.train.data_parallel import make_dp_vision_train_step
        step_fn = jax.jit(make_dp_vision_train_step(
            apply_fn, cfg, lb, regime, mesh, use_kernels=use_kernels,
            weight_decay=weight_decay))
    else:
        step_fn = jax.jit(make_vision_train_step(
            apply_fn, cfg, lb, regime, use_kernels=use_kernels,
            weight_decay=weight_decay))
    evaluate = make_vision_eval(apply_fn, cfg)

    x_tr, y_tr = data.x_train, data.y_train
    n = x_tr.shape[0]
    perm = _epoch_perm(shuffle_key, epoch, n)
    best = logger.max("val_acc")
    while step < regime.total_steps:
        b = (batch_schedule.batch_at(step) if batch_schedule is not None
             else lb.batch_size)
        if b > n:
            if mesh is not None:
                # capping would silently break the divisibility the mesh
                # gating validated against the CONFIGURED batch size
                raise ValueError(f"batch {b} > dataset {n} on a mesh run")
            b = n
        if cursor + b > n:
            epoch += 1
            cursor = 0
            perm = _epoch_perm(shuffle_key, epoch, n)
        idx = perm[cursor:cursor + b]
        cursor += b
        x = jnp.asarray(x_tr[idx])
        y = jnp.asarray(y_tr[idx])
        t0 = time.perf_counter()
        with tracer.span("train.step", step=step, batch=b):
            params, bn_state, opt_state, m = step_fn(
                params, bn_state, opt_state, x, y, jnp.int32(step),
                jax.random.fold_in(noise_key, step))
            if reg is not None:
                jax.block_until_ready(m["loss"])
        if reg is not None:
            _obs_step_metrics(reg, t0, m, b)
        if tracker is not None and _record_diffusion(
                step, regime.total_steps, diffusion_every):
            tracker.record(step + 1, params)
        if eval_every and step % eval_every == 0:
            with tracer.span("train.eval", step=step):
                acc = evaluate(params, bn_state, data.x_test, data.y_test)
            mh = jax.device_get(m)     # one sync for every logged metric
            logger.log(step, val_acc=acc, train_loss=float(mh["loss"]),
                       lr=float(mh["lr"]))
            best = max(best, acc)
            if log_fn:
                log_fn(f"step {step:5d} loss {float(mh['loss']):.4f} "
                       f"val_acc {acc:.4f} lr {float(mh['lr']):.4f}")
        step += 1
        if (checkpoint_dir and checkpoint_every
                and step % checkpoint_every == 0
                and step < regime.total_steps):
            _save_run_state(checkpoint_dir, step, params, bn_state,
                            opt_state, epoch=epoch, cursor=cursor,
                            logger=logger, tracker=tracker)
    final = evaluate(params, bn_state, data.x_test, data.y_test)
    train_acc = evaluate(params, bn_state, x_tr[:2048], y_tr[:2048])
    if tracker is not None:
        logger.set_series("distance", tracker.steps, tracker.distances)
    out = {"final_acc": final, "best_acc": max(best, final),
           "train_acc": train_acc, "history": logger.to_history(),
           "metrics": logger, "steps": step}
    if tracker is not None:
        out["log_fit"] = tracker.log_fit(burn_in=2)
        out["power_fit"] = tracker.power_fit(burn_in=2)
    return out


# ---------------------------------------------------------------------------
# LM training loop (the same recipe on the assigned architectures)
# ---------------------------------------------------------------------------


def train_lm(cfg: ModelConfig, lb: LargeBatchConfig, regime: Regime,
             rows: np.ndarray, *, seed: int = 0, eval_every: int = 0,
             holdout: int = 0, use_kernels: bool = False,
             weight_decay: float = 0.0, track_diffusion: bool = False,
             diffusion_every: int = 0,
             log_fn: Optional[Callable[[str], None]] = None,
             mesh=None,
             checkpoint_dir: Optional[str] = None,
             checkpoint_every: int = 0, resume: bool = True,
             obs=None) -> Dict[str, Any]:
    """LM twin of :func:`train_vision`: drives :func:`make_lm_train_step`
    over (N, seq_len) token rows with the same structured metrics,
    deterministic shuffling, and checkpoint/resume contract.

    ``holdout`` rows from the end are held out for CE evaluation.
    ``use_kernels=True`` (what the ``lm-smoke`` sweep runs) trains through
    the differentiable Pallas flash-attention and Mamba chunk-scan kernels.

    With ``mesh`` (mirroring :func:`train_vision`) the step runs through
    the unified 2-D layer (:mod:`repro.train.parallel`): batch over the dp
    axes, MoE expert weights over ``"model"``.

    ``obs`` mirrors :func:`train_vision`: ``train.step`` spans plus the
    ``train/*`` telemetry set in the registry.
    """
    init_key, noise_key, shuffle_key = jax.random.split(
        jax.random.PRNGKey(seed), 3)
    params = T.init_params(init_key, cfg)
    opt_state = sgd.init(params)
    tracker = DiffusionTracker(params) if track_diffusion else None
    params, opt_state, _, step, epoch, cursor, logger = \
        _restore_run_state(checkpoint_dir if resume else None,
                           params, opt_state, None, tracker)
    tracer = obs.tracer if obs is not None else NULL_TRACER
    reg = obs.registry if obs is not None else None
    if obs is not None:
        logger.attach_registry(obs.registry, prefix="train/")

    step_fn = jax.jit(make_lm_train_step(
        cfg, lb, regime, weight_decay=weight_decay,
        use_kernels=use_kernels, mesh=mesh,
        params=params if mesh is not None else None))
    eval_fn = jax.jit(make_lm_eval_step(cfg, use_kernels=use_kernels))

    train_rows = rows[: rows.shape[0] - holdout] if holdout else rows
    eval_rows = rows[rows.shape[0] - holdout:] if holdout else rows[:0]
    n = train_rows.shape[0]
    b = lb.batch_size
    if n < b:
        raise ValueError(f"{n} rows < batch_size {b}")

    def eval_ce() -> float:
        """Row-weighted mean CE over the WHOLE holdout: full batches of
        ``b`` plus the trailing remainder (one extra jit shape) — previously
        the tail rows were silently dropped whenever a full batch fit."""
        n_eval = eval_rows.shape[0]
        if n_eval == 0:
            return float("nan")
        total = 0.0
        for i in range(0, n_eval, b):
            chunk = eval_rows[i:i + b]
            ce = float(eval_fn(params, {"tokens": jnp.asarray(chunk)}))
            total += ce * chunk.shape[0]
        return total / n_eval

    perm = _epoch_perm(shuffle_key, epoch, n)
    while step < regime.total_steps:
        if cursor + b > n:
            epoch += 1
            cursor = 0
            perm = _epoch_perm(shuffle_key, epoch, n)
        idx = perm[cursor:cursor + b]
        cursor += b
        batch = {"tokens": jnp.asarray(train_rows[idx])}
        t0 = time.perf_counter()
        with tracer.span("train.step", step=step, batch=b):
            params, opt_state, m = step_fn(
                params, opt_state, batch, jnp.int32(step),
                jax.random.fold_in(noise_key, step))
            if reg is not None:
                jax.block_until_ready(m["loss"])
        if reg is not None:
            _obs_step_metrics(reg, t0, m, b)
        if tracker is not None and _record_diffusion(
                step, regime.total_steps, diffusion_every):
            tracker.record(step + 1, params)
        if eval_every and step % eval_every == 0:
            with tracer.span("train.eval", step=step):
                ce = eval_ce()
            mh = jax.device_get(m)     # one sync for every logged metric
            logger.log(step, eval_ce=ce, train_loss=float(mh["loss"]),
                       lr=float(mh["lr"]))
            if log_fn:
                log_fn(f"step {step:5d} loss {float(mh['loss']):.4f} "
                       f"eval_ce {ce:.4f}")
        step += 1
        if (checkpoint_dir and checkpoint_every
                and step % checkpoint_every == 0
                and step < regime.total_steps):
            _save_run_state(checkpoint_dir, step, params, None, opt_state,
                            epoch=epoch, cursor=cursor, logger=logger,
                            tracker=tracker)
    final_ce = eval_ce()
    if tracker is not None:
        logger.set_series("distance", tracker.steps, tracker.distances)
    out = {"final_ce": final_ce, "metrics": logger,
           "history": logger.to_history(), "steps": step}
    if tracker is not None:
        out["log_fit"] = tracker.log_fit(burn_in=2)
        out["power_fit"] = tracker.power_fit(burn_in=2)
    return out
