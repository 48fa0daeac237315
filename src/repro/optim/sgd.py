"""Momentum SGD — the paper's optimizer ("we focused on momentum SGD, with a
fixed learning rate that decreases exponentially every few epochs").

Integrates the large-batch toolkit: global-norm gradient clipping and
multiplicative (ghost) gradient noise are applied inside ``update`` so a
single LargeBatchConfig drives the whole recipe.

Optionally stores momentum in a block-wise int8 quantized form
(``momentum_dtype="int8"``) — a beyond-paper memory optimization used to fit
the 1T-param config's optimizer state in pod HBM (see EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.clipping import clip_by_global_norm
from repro.core.noise import multiplicative_noise_grads

Params = Any

_QBLOCK = 256


def _quantize_int8(x: jax.Array) -> Dict[str, jax.Array]:
    """Blockwise int8 along the LAST axis, keeping the leading dims — the
    quantized buffers then shard exactly like their parameter (flattening
    would force GSPMD reshards between the param and momentum layouts)."""
    xf = x.astype(jnp.float32)
    last = xf.shape[-1]
    pad = (-last) % _QBLOCK
    if pad:
        xf = jnp.pad(xf, [(0, 0)] * (xf.ndim - 1) + [(0, pad)])
    nb = xf.shape[-1] // _QBLOCK
    blocks = xf.reshape(xf.shape[:-1] + (nb, _QBLOCK))
    scale = jnp.max(jnp.abs(blocks), axis=-1, keepdims=True) / 127.0
    q = jnp.round(blocks / jnp.maximum(scale, 1e-12)).astype(jnp.int8)
    return {"q": q, "scale": scale.astype(jnp.float32)}


def _dequantize_int8(qs: Dict[str, jax.Array], shape, dtype) -> jax.Array:
    blocks = qs["q"].astype(jnp.float32) * qs["scale"]
    flat_last = blocks.reshape(blocks.shape[:-2]
                               + (blocks.shape[-2] * _QBLOCK,))
    out = flat_last[..., : shape[-1]]
    return out.reshape(shape).astype(dtype)


class SGDState(NamedTuple):
    momentum: Params
    step: jax.Array


def init(params: Params, momentum_dtype: str = "float32") -> SGDState:
    if momentum_dtype == "int8":
        mom = jax.tree.map(lambda p: _quantize_int8(jnp.zeros_like(p)), params)
    else:
        dt = jnp.dtype(momentum_dtype)
        mom = jax.tree.map(lambda p: jnp.zeros(p.shape, dtype=dt), params)
    return SGDState(momentum=mom, step=jnp.zeros((), jnp.int32))


@jax.named_scope("optimizer")
def update(grads: Params, state: SGDState, params: Params, *,
           lr: jax.Array, momentum: float = 0.9, nesterov: bool = False,
           weight_decay: float = 0.0, grad_clip: float = 0.0,
           noise_sigma: float = 0.0, rng: Optional[jax.Array] = None,
           momentum_dtype: str = "float32",
           ) -> Tuple[Params, SGDState, Dict[str, jax.Array]]:
    """One optimizer step. Returns (new_params, new_state, metrics)."""
    metrics: Dict[str, jax.Array] = {}
    if grad_clip and grad_clip > 0:
        grads, gnorm = clip_by_global_norm(grads, grad_clip)
        metrics["grad_norm"] = gnorm
    if noise_sigma and noise_sigma > 0:
        assert rng is not None, "gradient noise needs an rng"
        grads = multiplicative_noise_grads(rng, grads, noise_sigma)

    is_q = momentum_dtype == "int8"

    def one(p, g, m):
        gf = g.astype(jnp.float32)
        if weight_decay:
            gf = gf + weight_decay * p.astype(jnp.float32)
        mf = (_dequantize_int8(m, p.shape, jnp.float32) if is_q
              else m.astype(jnp.float32))
        mf = momentum * mf + gf
        step_dir = (gf + momentum * mf) if nesterov else mf
        new_p = (p.astype(jnp.float32) - lr * step_dir).astype(p.dtype)
        new_m = _quantize_int8(mf) if is_q else mf.astype(m.dtype)
        return new_p, new_m

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state.momentum)
    out = [one(p, g, m) for p, g, m in zip(flat_p, flat_g, flat_m)]
    new_params = jax.tree.unflatten(treedef, [o[0] for o in out])
    new_mom = jax.tree.unflatten(treedef, [o[1] for o in out])
    return new_params, SGDState(new_mom, state.step + 1), metrics
