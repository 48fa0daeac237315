"""Window driver for language-model training through
``make_lm_train_step``: seeded token rows, every step's rows distinct,
made on the host and put on the device each step.

Traffic keys: ``rows``, ``seq``, ``use_kernels``, ``remat``,
``ce_chunk``, ``base_batch``, ``lr_rule``, ``base_lr``, ``grad_clip``,
``momentum``, ``weight_decay``, ``checked_steps`` and ``limits``.
Configuration keys are the model's Hugging Face ``config.json`` names;
``program.arch`` names the program's own configuration of the model,
whose sizes are then set from the file.
"""
from __future__ import annotations

import dataclasses
import gc

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, training
from bench.counts import lm as counts
from bench.harness import Outcome


def model_config(cfg):
    """The program's ``ModelConfig`` with every size the file states."""
    from repro.configs.registry import get_config
    base = get_config(cfg["program"]["arch"])
    out = dataclasses.replace(
        base, d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        body_repeats=cfg["num_hidden_layers"], rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"], dtype=cfg["torch_dtype"],
        norm=dataclasses.replace(base.norm, eps=cfg["rms_norm_eps"]))
    if len(out.layers) != cfg["num_hidden_layers"] or not out.qk_norm:
        raise ValueError(f"{base.name} is not a qk-norm stack of "
                         f"{cfg['num_hidden_layers']} layers")
    return out


class Feed:
    """Seeded token rows; step ``i`` draws its own."""

    def __init__(self, cfg, tr, seed: int):
        self.shape = (tr["rows"], tr["seq"])
        self.vocab = cfg["vocab_size"]
        self.seed = seed
        self.key = jax.random.PRNGKey(seed)

    def host(self, i: int) -> np.ndarray:
        return np.random.default_rng([self.seed, 2, i]).integers(
            0, self.vocab, self.shape, dtype=np.int32)

    def __call__(self, i: int):
        return ({"tokens": jax.device_put(self.host(i))}, jnp.int32(i),
                jax.random.fold_in(self.key, 1 + i))


def program(cfg, tr, ref, wrap=None) -> training.Program:
    """The compiled ``make_lm_train_step`` of the cell (``wrap``ped, for a
    test that plants a fault), its state made from the reference's
    weights."""
    from repro.core import LargeBatchConfig, Regime
    from repro.models import transformer as T
    from repro.optim import sgd
    from repro.train.trainer import make_lm_train_step
    mcfg = model_config(cfg)
    key = jax.random.PRNGKey(0)
    training.same_layout(
        jax.eval_shape(lambda k: T.init_params(k, mcfg), key),
        jax.eval_shape(lambda k: ref.init(k, cfg), key))
    lb = LargeBatchConfig(
        batch_size=tr["rows"], base_batch_size=tr["base_batch"],
        lr_rule=tr["lr_rule"], grad_clip=tr["grad_clip"],
        momentum=tr["momentum"])
    regime = lb.build_regime(Regime(base_lr=tr["base_lr"],
                                    total_steps=10 ** 6, drop_every=10 ** 6))
    step_fn = make_lm_train_step(
        mcfg, lb, regime, weight_decay=tr["weight_decay"],
        use_kernels=tr["use_kernels"], remat=tr["remat"],
        ce_chunk=tr["ce_chunk"])
    if wrap is not None:
        step_fn = wrap(step_fn)
    example = ({"tokens": np.zeros((tr["rows"], tr["seq"]), np.int32)},
               jnp.int32(0), key)
    return training.Program(step_fn, lambda k: ref.init(k, cfg),
                            lambda params: (params, sgd.init(params)),
                            example, lambda st: st[1].momentum,
                            lambda st: st[0])


def reference(ref, cfg, tr, prog_, seed: int, feed: Feed,
              compute: str = "f32", loss_rows: int = 0):
    """The plain reference's readings from the weights of ``seed`` over the
    checked steps' batches, its matmuls in ``compute``; ``loss_rows``
    leaves all but the first rows of each batch out of the loss."""
    batches = [feed.host(i) for i in range(tr["checked_steps"])]
    return ref.Trainer(cfg, tr, compute, loss_rows).run(
        prog_.params(seed), batches)


def calibrate(spec, ref, seeds):
    """Readings at the cell's own size, seed by seed: the program against
    the plain reference, and in the program's place the control (the
    reference with fp8 matmuls) and the reference with half of each batch
    left out of the loss."""
    cfg, tr = spec.config, spec.traffic
    prog_ = program(cfg, tr, ref)
    for seed in seeds:
        feed = Feed(cfg, tr, seed)
        steps, prog = prog_.start(seed, feed, tr["checked_steps"])
        del steps
        gc.collect()
        want = reference(ref, cfg, tr, prog_, seed, feed)
        row = {"seed": seed, "program": compare.train_numbers(prog, want),
               "leaves": {"program": prog, "reference": want}}
        print(f"calibrate seed {seed}: program {row['program']}", flush=True)
        row["control"] = compare.train_numbers(
            reference(ref, cfg, tr, prog_, seed, feed, "fp8"), want)
        row["half_batch"] = compare.train_numbers(
            reference(ref, cfg, tr, prog_, seed, feed,
                      loss_rows=tr["rows"] // 2),
            want)
        yield row


def run(cell) -> Outcome:
    cfg, tr = cell.config, cell.traffic
    prog_ = program(cfg, tr, cell.reference)
    hlo = {"step": prog_.compiled.as_text()} if cell.trace else {}
    feed = Feed(cfg, tr, cell.seed)
    steps, prog = prog_.start(cell.seed, feed, tr["checked_steps"])
    per_step = tr["rows"] * tr["seq"]
    win = training.window(cell, steps, per_step)
    del steps
    prog_.compiled = None
    gc.collect()

    want = reference(cell.reference, cfg, tr, prog_, cell.seed, feed)
    facts = {"steps": win["steps"], "items": win["items"],
             "flops_per_item": counts.train_flops_per_token(cfg, tr["seq"]),
             "hlo": hlo}
    failed = sum(not np.isfinite(v) for v in win["losses"])
    return Outcome({"train_tokens_per_s": win["rate"]}, win["steps"], failed,
                   compare.train_checks(prog, want, tr["limits"]), facts)
