"""Adam — the adaptive baseline the paper contrasts with ("many current
studies still use simple variants of SGD ... due to the tendency of these
methods to converge to a lower test error")."""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.clipping import clip_by_global_norm

Params = Any


class AdamState(NamedTuple):
    mu: Params
    nu: Params
    step: jax.Array


def init(params: Params) -> AdamState:
    z = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return AdamState(mu=z, nu=jax.tree.map(jnp.zeros_like, z),
                     step=jnp.zeros((), jnp.int32))


@jax.named_scope("optimizer")
def update(grads: Params, state: AdamState, params: Params, *,
           lr: jax.Array, b1: float = 0.9, b2: float = 0.999,
           eps: float = 1e-8, weight_decay: float = 0.0,
           grad_clip: float = 0.0,
           ) -> Tuple[Params, AdamState, Dict[str, jax.Array]]:
    metrics: Dict[str, jax.Array] = {}
    if grad_clip and grad_clip > 0:
        grads, gnorm = clip_by_global_norm(grads, grad_clip)
        metrics["grad_norm"] = gnorm
    t = state.step + 1
    tf = t.astype(jnp.float32)

    def one(p, g, mu, nu):
        gf = g.astype(jnp.float32)
        if weight_decay:
            gf = gf + weight_decay * p.astype(jnp.float32)
        mu2 = b1 * mu + (1 - b1) * gf
        nu2 = b2 * nu + (1 - b2) * jnp.square(gf)
        mu_hat = mu2 / (1 - b1 ** tf)
        nu_hat = nu2 / (1 - b2 ** tf)
        new_p = (p.astype(jnp.float32)
                 - lr * mu_hat / (jnp.sqrt(nu_hat) + eps)).astype(p.dtype)
        return new_p, mu2, nu2

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_mu = treedef.flatten_up_to(state.mu)
    flat_nu = treedef.flatten_up_to(state.nu)
    out = [one(*args) for args in zip(flat_p, flat_g, flat_mu, flat_nu)]
    new_params = jax.tree.unflatten(treedef, [o[0] for o in out])
    new_mu = jax.tree.unflatten(treedef, [o[1] for o in out])
    new_nu = jax.tree.unflatten(treedef, [o[2] for o in out])
    return new_params, AdamState(new_mu, new_nu, t), metrics
