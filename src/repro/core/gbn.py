"""Ghost Batch Normalization (Hoffer et al. 2017, Algorithm 1).

The large batch ``B_L`` is scattered into virtual ("ghost") batches of size
``|B_S|``; normalization statistics are computed **per ghost batch** during
training, while inference uses the running (full-batch) statistics, exactly
as the paper prescribes ("it is important to use the full batch statistic
... for the inference phase").

Running statistics follow the paper's cascaded EMA:

    mu_run <- (1-eta)^G mu_run + sum_{i=1..G} (1-eta)^{G-i} eta mu_B^i

i.e. the ghost batches are absorbed *sequentially* (equivalent closed form),
NOT by weighting each ghost batch equally — the paper reports that the
equal-weight variant used by the commercial frameworks "worsen[s] the
generalization performance".

Layout convention: x has shape (batch, ...features); statistics are computed
over the batch axis *and* all non-channel feature axes (NHWC convs reduce
over N,H,W per channel). The batch axis must be divisible by the ghost size
(use `num_ghosts` semantics below).

The compute-heavy normalization is also available as a Pallas TPU kernel
(`repro.kernels.gbn` / `ops.gbn_forward`), validated against this reference.
The kernel path is fully differentiable (dedicated Pallas backward via
``jax.custom_vjp``), so ``use_kernels=True`` is safe under ``jax.grad`` —
including the leftover-rows tail below, which back-propagates through the
kernel's mu/var outputs.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


def gbn_init(n_features: int) -> Tuple[Params, Params]:
    """Returns (learnable params, running state)."""
    params = {
        "gamma": jnp.ones((n_features,), jnp.float32),
        "beta": jnp.zeros((n_features,), jnp.float32),
    }
    state = {
        "mu_run": jnp.zeros((n_features,), jnp.float32),
        "var_run": jnp.ones((n_features,), jnp.float32),
        "initialized": jnp.zeros((), jnp.bool_),
    }
    return params, state


def _ghost_stats(xg: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """xg: (G, ghost_rows, C) -> per-ghost mean/var (G, C)."""
    mu = jnp.mean(xg, axis=1)
    var = jnp.mean(jnp.square(xg - mu[:, None, :]), axis=1)
    return mu, var


def _cascaded_ema(run: jax.Array, per_ghost: jax.Array, eta: float) -> jax.Array:
    """Closed form of sequentially folding G ghost statistics into the EMA:
    run <- (1-eta)^G run + eta * sum_i (1-eta)^(G-1-i) stats_i."""
    G = per_ghost.shape[0]
    decay = (1.0 - eta) ** jnp.arange(G - 1, -1, -1, dtype=jnp.float32)
    return (1.0 - eta) ** G * run + eta * jnp.einsum(
        "g,gc->c", decay, per_ghost)


@jax.named_scope("gbn")
def gbn_apply(params: Params, state: Params, x: jax.Array, *,
              ghost_batch_size: int, eps: float = 1e-5,
              momentum: float = 0.1, training: bool = True,
              use_kernels: bool = False) -> Tuple[jax.Array, Params]:
    """Apply GBN over x: (B, ..., C). Returns (y, new_state).

    During training, batch rows are scattered into G = B // ghost_batch_size
    ghost batches (B < ghost_batch_size uses a single ghost batch = plain BN,
    the small-batch limit the paper matches).
    """
    orig_shape = x.shape
    Bsz, C = x.shape[0], x.shape[-1]
    dt = x.dtype
    gamma = params["gamma"].astype(jnp.float32)
    beta = params["beta"].astype(jnp.float32)

    if not training:
        mu, var = state["mu_run"], state["var_run"]
        y = (x.astype(jnp.float32) - mu) * jax.lax.rsqrt(var + eps)
        return (y * gamma + beta).astype(dt), state

    gbs = min(ghost_batch_size, Bsz)
    G = Bsz // gbs
    rows = G * gbs
    # fold all non-channel feature dims into the row axis per ghost
    xg = x[:rows].astype(jnp.float32).reshape(G, gbs, -1, C).reshape(G, -1, C)

    if use_kernels:
        from repro.kernels import ops as kops
        y, mu, var = kops.gbn_forward(xg, gamma, beta, eps=eps)
    else:
        mu, var = _ghost_stats(xg)
        y = (xg - mu[:, None, :]) * jax.lax.rsqrt(var[:, None, :] + eps)
        y = y * gamma + beta

    y = y.reshape((rows,) + orig_shape[1:])
    if rows < Bsz:  # leftover rows normalized with the last ghost's stats
        tail = (x[rows:].astype(jnp.float32) - mu[-1]) \
            * jax.lax.rsqrt(var[-1] + eps) * gamma + beta
        y = jnp.concatenate([y, tail], axis=0)

    # paper's cascaded EMA (unbiased var for the running estimate)
    n = xg.shape[1]
    var_unbiased = var * (n / max(n - 1, 1))
    first = ~state["initialized"]
    mu_run = jnp.where(first, mu.mean(0),
                       _cascaded_ema(state["mu_run"], mu, momentum))
    var_run = jnp.where(first, var_unbiased.mean(0),
                        _cascaded_ema(state["var_run"], var_unbiased, momentum))
    new_state = {"mu_run": mu_run, "var_run": var_run,
                 "initialized": jnp.ones((), jnp.bool_)}
    return y.astype(dt), new_state


@jax.named_scope("gbn")
def equal_weight_bn_apply(params: Params, state: Params, x: jax.Array, *,
                          eps: float = 1e-5, momentum: float = 0.1,
                          training: bool = True) -> Tuple[jax.Array, Params]:
    """Conventional BatchNorm over the *full* batch with the equal-weight
    running update — the baseline GBN is compared against (what the paper
    calls the commercial-framework behaviour)."""
    dt = x.dtype
    gamma = params["gamma"].astype(jnp.float32)
    beta = params["beta"].astype(jnp.float32)
    if not training:
        mu, var = state["mu_run"], state["var_run"]
        y = (x.astype(jnp.float32) - mu) * jax.lax.rsqrt(var + eps)
        return (y * gamma + beta).astype(dt), state
    C = x.shape[-1]
    xf = x.astype(jnp.float32).reshape(-1, C)
    mu = xf.mean(0)
    var = jnp.mean(jnp.square(xf - mu), axis=0)
    y = (x.astype(jnp.float32) - mu) * jax.lax.rsqrt(var + eps) * gamma + beta
    n = xf.shape[0]
    var_u = var * (n / max(n - 1, 1))
    first = ~state["initialized"]
    mu_run = jnp.where(first, mu,
                       (1 - momentum) * state["mu_run"] + momentum * mu)
    var_run = jnp.where(first, var_u,
                        (1 - momentum) * state["var_run"] + momentum * var_u)
    return y.astype(dt), {"mu_run": mu_run, "var_run": var_run,
                          "initialized": jnp.ones((), jnp.bool_)}
