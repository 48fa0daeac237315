"""The window of a training cell, shared by the training drivers.

One object, the compiled step with its state, is built in set-up, driven
from the seed through its first steps by the same call and feed as the
window's, and handed on to the window. Those first steps are what the plain
reference follows after the window: their losses, the optimizer's state
after step 1 and the parameters after the last of them are read on the way.
"""
from __future__ import annotations

import collections
from typing import Any, Callable, Dict, List

import jax
import jax.numpy as jnp


def leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for a in jax.tree.leaves(tree)]


def same_layout(program_tree, reference_tree) -> None:
    """The reference makes the weights in the program's layout; refuse to
    run where the two disagree on a leaf's path, shape or type."""
    def table(tree):
        return {jax.tree_util.keystr(k): (v.shape, v.dtype)
                for k, v in jax.tree_util.tree_leaves_with_path(tree)}
    a, b = table(program_tree), table(reference_tree)
    if a != b:
        diff = sorted(set(a.items()) ^ set(b.items()))[:6]
        raise ValueError(f"the program's parameter layout is not the "
                         f"reference's: {diff}")


class Steps:
    """Drives ``compiled(*state, *feed(i)) -> (*state, metrics)``, keeping at
    most ``depth`` steps in flight, as a training loop that reads its loss
    a step or two behind does."""

    def __init__(self, compiled, state: tuple, feed: Callable[[int], tuple],
                 depth: int = 2):
        self.compiled = compiled
        self.state = state
        self.feed = feed
        self.depth = depth
        self.i = 0
        self.inflight: collections.deque = collections.deque()

    def step(self) -> Any:
        args = self.feed(self.i)
        *state, metrics = self.compiled(*self.state, *args)
        self.state = tuple(state)
        self.i += 1
        self.inflight.append(metrics)
        while len(self.inflight) > self.depth:
            jax.block_until_ready(self.inflight.popleft())
        return metrics

    def drain(self) -> None:
        while self.inflight:
            jax.block_until_ready(self.inflight.popleft())


class Program:
    """The compiled step of a cell and what reads its state; compiled once,
    started from any seed.

    ``make_weights(key)`` makes the weights on the device in one call, and
    ``make_state(weights)`` the step's state from them (a tuple, the
    parameters and the optimizer's state among it); ``example`` holds the
    arguments that follow the state, for lowering. ``momentum_of(state)``
    and ``params_of(state)`` pick the optimizer's momentum and the
    parameters; ``params_of_weights(weights)`` the parameters of the
    weights. The plain reference starts from ``make_weights`` too: one
    compiled call gives both sides the same bits."""

    def __init__(self, step_fn: Callable, make_weights: Callable,
                 make_state: Callable, example: tuple,
                 momentum_of: Callable, params_of: Callable,
                 params_of_weights: Callable = lambda w: w):
        self.make_weights = jax.jit(make_weights)
        self.make_state = jax.jit(make_state)
        self.params_of_weights = params_of_weights
        shapes = jax.eval_shape(lambda k: make_state(make_weights(k)),
                                jax.random.PRNGKey(0))
        self.compiled = jax.jit(
            step_fn, donate_argnums=tuple(range(len(shapes)))).lower(
                *shapes, *example).compile()
        self.read_mom = jax.jit(lambda st: leaf_norms(momentum_of(st)))
        self._change = jax.jit(lambda st, p0: leaf_norms(jax.tree.map(
            jnp.subtract, params_of(st), p0)))

    def params(self, seed: int):
        """The parameters as made from ``seed``."""
        return self.params_of_weights(
            self.make_weights(jax.random.PRNGKey(seed)))

    def start(self, seed: int, feed: Callable[[int], tuple], n: int):
        """The step and its state from ``seed``, driven through its first
        ``n`` steps: (steps, the program's readings)."""
        weights = self.make_weights(jax.random.PRNGKey(seed))
        steps = Steps(self.compiled, self.make_state(weights), feed)
        del weights
        prog = first_steps(steps, n, self.read_mom,
                           lambda st: self._change(st, self.params(seed)))
        return steps, prog


def first_steps(steps: Steps, n: int, read_mom: Callable,
                read_change: Callable) -> Dict[str, Any]:
    """Take the first ``n`` steps; read each loss, the per-leaf norms of the
    optimizer's momentum after step 1 (read by ``read_mom(state)``) and of
    the parameters' change after step ``n`` (``read_change(state)``),
    before the next step takes the buffers over."""
    out: Dict[str, Any] = {"loss": []}
    for i in range(n):
        m = steps.step()
        steps.drain()
        out["loss"].append(float(jax.device_get(m["loss"])))
        if i == 0:
            out["mom1"] = [float(v) for v in
                           jax.device_get(read_mom(steps.state))]
    out["change"] = [float(v) for v in
                     jax.device_get(read_change(steps.state))]
    return out


def window(cell, steps: Steps, per_step: int) -> Dict[str, Any]:
    """Step until the window's time is up; the window closes at the end of
    the last step. Returns the steps taken, the items they held, the rate
    over the whole window and each step's loss."""
    metrics: List[Any] = []
    with cell.window() as w:
        while w.open():
            with cell.span("bench.step"):
                metrics.append(steps.step())
        steps.drain()
    losses = [float(m["loss"]) for m in jax.device_get(metrics)]
    return {"steps": len(metrics), "items": len(metrics) * per_step,
            "rate": len(metrics) * per_step / cell.window_s,
            "losses": losses}
