"""Pallas TPU kernel for fused residual-add + RMSNorm.

The transformer block's second sublayer boundary does two passes over the
(B*T, d) activations: ``s = x + y_mixer`` (residual add) then
``h = rmsnorm(s) * scale``. This kernel folds both into ONE pass: each
row tile is read once, the residual sum ``s`` (the new residual stream —
a live output, it feeds the next sublayer's add) and the normalized ``h``
are written together, halving the HBM round-trips at the sublayer seam.

Layout: rows = flattened (B*T) on the sublane axis (tiled), the full
``d_model`` axis on the lane axis — ``d`` must be a 128-multiple
(``ops._fused_tile`` gates this; non-aligned widths fall back to the jnp
oracle with a one-time warning). Rows are zero-padded to the row tile.

Backward (`rmsnorm_residual_backward_pallas`): one pass over the same
grid. Both forward outputs carry live cotangents (``dy`` on the normed
activations, ``ds`` on the emitted residual stream). With
``rv = rsqrt(mean(s^2) + eps)``, ``s_hat = s * rv`` and ``w = dy * scale``:

    dx = dr = rv * w - rv * s_hat * mean(w * s_hat) + ds

and ``dscale = sum_rows(dy * s_hat)`` accumulates across row tiles
directly in a ``(1, d)`` output block whose index map is constant over
the row-tile grid axis (the consecutive-revisit pattern of the GBN
reduction). Residuals saved: ``(s, scale)`` — nothing beyond the live
residual stream.

Public entry: :func:`repro.kernels.ops.rmsnorm_residual` (custom_vjp).
Oracle: :func:`repro.kernels.ref.rmsnorm_residual_ref`.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_ROW_TILE = 128


def _pad_rows(x: jax.Array, mult: int) -> jax.Array:
    pad = (-x.shape[0]) % mult
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


def _fwd_kernel(x_ref, r_ref, scale_ref, y_ref, s_ref, *, eps: float):
    s = x_ref[...] + r_ref[...]                 # residual add, input dtype
    s_ref[...] = s
    sf = s.astype(jnp.float32)
    var = jnp.mean(sf * sf, axis=-1, keepdims=True)
    y = sf * jax.lax.rsqrt(var + eps) * scale_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)


def _bwd_kernel(s_ref, scale_ref, dy_ref, ds_ref, dx_ref, dscale_ref, *,
                eps: float):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dscale_ref[...] = jnp.zeros_like(dscale_ref)

    sf = s_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    rv = jax.lax.rsqrt(jnp.mean(sf * sf, axis=-1, keepdims=True) + eps)
    s_hat = sf * rv
    w = dy * scale_ref[...].astype(jnp.float32)
    ds_norm = rv * (w - s_hat * jnp.mean(w * s_hat, axis=-1, keepdims=True))
    dx = ds_norm + ds_ref[...].astype(jnp.float32)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    # padded rows have dy == 0, so they add nothing here
    dscale_ref[...] += jnp.sum(dy * s_hat, axis=0, keepdims=True)


def rmsnorm_residual_pallas(x: jax.Array, r: jax.Array, scale: jax.Array, *,
                            eps: float = 1e-6,
                            row_tile: int = DEFAULT_ROW_TILE,
                            interpret: bool = False
                            ) -> Tuple[jax.Array, jax.Array]:
    """x, r: (N, d) with d a 128-multiple; scale: (d,).

    Returns (y = rmsnorm(x + r) * scale, s = x + r), both (N, d) in
    x.dtype.
    """
    N, d = x.shape
    xp = _pad_rows(x, row_tile)
    rp = _pad_rows(r, row_tile)
    nr = xp.shape[0] // row_tile
    row_spec = pl.BlockSpec((row_tile, d), lambda i: (i, 0))
    y, s = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        name="rmsnorm_residual_fwd",
        grid=(nr,),
        in_specs=[row_spec, row_spec,
                  pl.BlockSpec((1, d), lambda i: (0, 0))],
        out_specs=[row_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct(xp.shape, x.dtype),
                   jax.ShapeDtypeStruct(xp.shape, x.dtype)],
        interpret=interpret,
    )(xp, rp, scale.reshape(1, d))
    return y[:N], s[:N]


def rmsnorm_residual_backward_pallas(s: jax.Array, scale: jax.Array,
                                     dy: jax.Array, ds: jax.Array, *,
                                     eps: float = 1e-6,
                                     row_tile: int = DEFAULT_ROW_TILE,
                                     interpret: bool = False
                                     ) -> Tuple[jax.Array, jax.Array]:
    """VJP of :func:`rmsnorm_residual_pallas` from the saved ``(s, scale)``.

    s, dy, ds: (N, d); returns (dx (N, d) in s.dtype — ``dr`` is the same
    array, the residual add fans the cotangent out equally — and
    dscale (d,) f32).
    """
    N, d = s.shape
    sp = _pad_rows(s, row_tile)
    dyp = _pad_rows(dy, row_tile)
    dsp = _pad_rows(ds, row_tile)
    nr = sp.shape[0] // row_tile
    row_spec = pl.BlockSpec((row_tile, d), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, d), lambda i: (0, 0))
    dx, dscale = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps),
        name="rmsnorm_residual_bwd",
        grid=(nr,),
        in_specs=[row_spec, vec_spec, row_spec, row_spec],
        out_specs=[row_spec, vec_spec],
        out_shape=[jax.ShapeDtypeStruct(sp.shape, s.dtype),
                   jax.ShapeDtypeStruct((1, d), jnp.float32)],
        interpret=interpret,
    )(sp, scale.reshape(1, d), dyp, dsp)
    return dx[:N], dscale.reshape(d)
