"""Window driver for training the paper's vision models through
``make_vision_train_step``, fed as ``train_vision`` feeds it: a seeded
host-resident data set, each step's batch gathered on the host by a
per-epoch permutation and put on the device.

Traffic keys: ``batch``, ``ghost``, ``use_gbn``, ``base_batch``,
``lr_rule``, ``regime_adaptation``, ``grad_clip``, ``momentum``,
``weight_decay``, ``base_lr``, ``drop_every``, ``drop_factor``,
``use_kernels``, ``dataset_size``, ``checked_steps``, ``reference_rows``
and ``limits`` (of :mod:`bench.compare`'s numbers).
"""
from __future__ import annotations

import gc

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, training
from bench.counts import vision as counts
from bench.harness import Outcome


class Feed:
    """The seeded data set and the batches of each step."""

    def __init__(self, cfg, tr, seed: int):
        rng = np.random.default_rng([seed, 0])
        n = tr["dataset_size"]
        self.x = rng.standard_normal((n, *cfg["input_shape"]),
                                     dtype=np.float32)
        self.y = rng.integers(0, cfg["n_classes"], n, dtype=np.int32)
        self.batch = tr["batch"]
        self.seed = seed
        self.key = jax.random.PRNGKey(seed)
        self._perm = {}

    def rows(self, i: int) -> np.ndarray:
        per_epoch = self.x.shape[0] // self.batch
        epoch, k = divmod(i, per_epoch)
        if epoch not in self._perm:
            self._perm = {epoch: np.random.default_rng(
                [self.seed, 1, epoch]).permutation(self.x.shape[0])}
        return self._perm[epoch][k * self.batch:(k + 1) * self.batch]

    def host(self, i: int):
        idx = self.rows(i)
        return self.x[idx], self.y[idx]

    def __call__(self, i: int):
        """Step ``i``'s arguments after the state, on the device."""
        x, y = self.host(i)
        return (jax.device_put(x), jax.device_put(y), jnp.int32(i),
                jax.random.fold_in(self.key, 1 + i))


def program(cfg, tr, ref, wrap=None) -> training.Program:
    """The compiled ``make_vision_train_step`` of the cell (``wrap``ped, for
    a test that plants a fault), its state made from the reference's
    weights."""
    from repro.configs.paper_models import VisionModelConfig
    from repro.core import LargeBatchConfig, Regime
    from repro.models.cnn import model_fns
    from repro.optim import sgd
    from repro.train.trainer import make_vision_train_step
    vcfg = VisionModelConfig(
        name=cfg["name"], kind=cfg["kind"],
        input_shape=tuple(cfg["input_shape"]), n_classes=cfg["n_classes"],
        channels=tuple(cfg["channels"]),
        blocks_per_stage=cfg["blocks_per_stage"], norm=cfg["norm"],
        ghost_batch_size=tr["ghost"], bn_momentum=cfg["bn_momentum"])
    lb = LargeBatchConfig(
        batch_size=tr["batch"], base_batch_size=tr["base_batch"],
        lr_rule=tr["lr_rule"], ghost_batch_size=tr["ghost"],
        use_gbn=tr["use_gbn"], regime_adaptation=tr["regime_adaptation"],
        grad_clip=tr["grad_clip"], momentum=tr["momentum"])
    regime = lb.build_regime(Regime(base_lr=tr["base_lr"],
                                    total_steps=10 ** 6,
                                    drop_every=tr["drop_every"],
                                    drop_factor=tr["drop_factor"]))
    init_fn, apply_fn = model_fns(vcfg)
    key = jax.random.PRNGKey(0)
    training.same_layout(jax.eval_shape(lambda k: init_fn(k, vcfg), key),
                         jax.eval_shape(lambda k: ref.init(k, cfg), key))

    step_fn = make_vision_train_step(
        apply_fn, vcfg, lb, regime, weight_decay=tr["weight_decay"],
        use_kernels=tr["use_kernels"])
    if wrap is not None:
        step_fn = wrap(step_fn)

    def make_state(weights):
        params, bn = weights
        return params, bn, sgd.init(params)

    example = (np.zeros((tr["batch"], *cfg["input_shape"]), np.float32),
               np.zeros((tr["batch"],), np.int32), jnp.int32(0), key)
    return training.Program(step_fn, lambda k: ref.init(k, cfg), make_state,
                            example, lambda st: st[2].momentum,
                            lambda st: st[0], lambda w: w[0])


def reference(ref, cfg, tr, params, feed: Feed, dtype=jnp.float32,
              loss_rows: int = 0):
    """The plain reference's readings from ``params`` over the checked
    steps' batches, in ``dtype``; ``loss_rows`` leaves all but the first
    rows of each batch out of the loss."""
    batches = [feed.host(i) for i in range(tr["checked_steps"])]
    return ref.Trainer(cfg, tr, dtype, loss_rows).run(params, batches)


def calibrate(spec, ref, seeds):
    """Readings of the comparison's numbers at the cell's own size, seed by
    seed: the program against the reference, and in the program's place
    the control (the reference in bfloat16) and the reference with half of
    each batch left out of the loss."""
    cfg, tr = spec.config, spec.traffic
    prog_ = program(cfg, tr, ref)
    for seed in seeds:
        feed = Feed(cfg, tr, seed)
        steps, prog = prog_.start(seed, feed, tr["checked_steps"])
        del steps
        params = prog_.params(seed)
        want = reference(ref, cfg, tr, params, feed)
        yield {"seed": seed,
               "program": compare.train_numbers(prog, want),
               "control": compare.train_numbers(reference(
                   ref, cfg, tr, params, feed, jnp.bfloat16), want),
               "half_batch": compare.train_numbers(reference(
                   ref, cfg, tr, params, feed, loss_rows=tr["batch"] // 2),
                   want)}


def run(cell) -> Outcome:
    cfg, tr = cell.config, cell.traffic
    prog_ = program(cfg, tr, cell.reference)
    hlo = {"step": prog_.compiled.as_text()} if cell.trace else {}
    feed = Feed(cfg, tr, cell.seed)
    steps, prog = prog_.start(cell.seed, feed, tr["checked_steps"])
    win = training.window(cell, steps, tr["batch"])
    del steps
    prog_.compiled = None
    gc.collect()

    want = reference(cell.reference, cfg, tr, prog_.params(cell.seed), feed)
    facts = {"steps": win["steps"], "items": win["items"],
             "flops_per_item": counts.train_flops_per_image(cfg),
             "hlo": hlo}
    failed = sum(not np.isfinite(v) for v in win["losses"])
    return Outcome({"images_per_s": win["rate"]}, win["steps"], failed,
                   compare.train_checks(prog, want, tr["limits"]), facts)
