"""Faults planted underneath the timed path, for the tests: each wraps the
program's step as the window calls it."""
from __future__ import annotations


def state_unchanged(real):
    """A step that computes, then hands back its input state unchanged."""
    def step(*args):
        out = real(*args)
        return (*args[:len(out) - 1], out[-1])
    return step


def half_batch_vision(real):
    """A vision step that leaves half of its batch out of the loss."""
    def step(params, bn, opt, x, y, i, rng):
        h = x.shape[0] // 2
        return real(params, bn, opt, x[:h], y[:h], i, rng)
    return step


def half_batch_lm(real):
    """A language-model step that leaves half of its rows out of the loss."""
    def step(params, opt, batch, i, rng):
        h = batch["tokens"].shape[0] // 2
        return real(params, opt, {"tokens": batch["tokens"][:h]}, i, rng)
    return step


def altered_tokens(make_serve_step):
    """The engine's decode step with every token it produces moved by one."""
    def make(cfg, *a, **kw):
        real = make_serve_step(cfg, *a, **kw)

        def step(*args, **kws):
            toks, cache = real(*args, **kws)
            return (toks + 1) % cfg.vocab_size, cache
        return step
    return make
