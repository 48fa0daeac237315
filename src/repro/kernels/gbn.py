"""Pallas TPU kernel for Ghost Batch Normalization (the paper's Algorithm 1
hot loop).

TPU-native design (not a CUDA port): two single-purpose kernels —
a tiled reduction producing per-(ghost, channel-tile) sums, and an
elementwise normalize — each gridded over (ghost, channel-tile, row-tile)
with VMEM-resident blocks. Channel tiles are multiples of 128 (VPU lane
width); row tiles bound the VMEM working set regardless of how many
rows (ghost_batch * H * W for convs) one ghost batch folds in.

Layout (what makes the blocks legal for Mosaic): per-(ghost, channel)
statistics travel as ``(G, 1, C)`` arrays, so a stat block ``(1, 1, tile)``
has the whole unit axis as its second-minor dimension. A channel axis
narrower than one 128-lane vreg (ResNet44's 16/32/64) is folded: ``128 // C``
consecutive rows share one lane row, ``(G, R, C) -> (G, R*C/128, 128)``, so
every block is lane-dense and no channel padding inflates the activations;
the per-lane sums are folded back to channels outside the kernels.

Public entry point: :func:`repro.kernels.ops.gbn_forward` (jit'd, falls back
to interpret mode off-TPU). Oracle: :func:`repro.kernels.ref.gbn_ref`.

Kernel gradients
----------------
``gbn_forward`` is fully differentiable: :mod:`repro.kernels.ops` wires
:func:`gbn_backward_pallas` up as the ``jax.custom_vjp`` rule, so
``jax.grad`` through the ``use_kernels=True`` training path never falls back
to autodiff-through-interpret. The backward mirrors the forward's structure:

1. a tiled reduction over the same (ghost, col-tile, row-tile) grid
   accumulating the two per-(ghost, channel) sums the BN backward needs,
   ``sum_r dy`` and ``sum_r dy * xhat`` (``xhat`` recomputed in-kernel from
   the saved mu/var — nothing bigger than the activations is stashed);
2. tiny (G, C)-shaped host math folding those sums (plus any upstream
   cotangents on the mu/var outputs — the leftover-rows path in
   :mod:`repro.core.gbn` genuinely propagates these) into three
   per-(ghost, channel) coefficients;
3. an elementwise pass over the same grid computing
   ``dx = dy*c1 + (x - mu)*c2 + c3``.

``dgamma``/``dbeta`` are the per-ghost sums reduced over ghosts. Oracle:
:func:`repro.kernels.ref.gbn_vjp_ref` (hand-derived pure jnp), cross-checked
against ``jax.vjp`` of :func:`repro.kernels.ref.gbn_ref` in the tests.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_ROW_TILE = 512
DEFAULT_COL_TILE = 128


def _stats_kernel(x_ref, sum_ref, sq_ref, *, n_rows: int):
    """Accumulate per-(ghost, col-tile) sum and sum-of-squares over row tiles.

    grid = (G, n_col_tiles, n_row_tiles); the row-tile axis is innermost so
    the (1, 1, col_tile) accumulators persist in VMEM across row steps.
    """
    r = pl.program_id(2)

    @pl.when(r == 0)
    def _init():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        sq_ref[...] = jnp.zeros_like(sq_ref)

    x = x_ref[0].astype(jnp.float32)                  # (row_tile, col_tile)
    # mask padded rows in the last row tile
    row0 = r * x.shape[0]
    valid = (row0 + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)) < n_rows
    x = jnp.where(valid, x, 0.0)
    sum_ref[0] += jnp.sum(x, axis=0, keepdims=True)
    sq_ref[0] += jnp.sum(x * x, axis=0, keepdims=True)


def _normalize_kernel(x_ref, mu_ref, var_ref, gamma_ref, beta_ref, y_ref, *,
                      eps: float):
    x = x_ref[0].astype(jnp.float32)                  # (row_tile, col_tile)
    mu = mu_ref[0]                                    # (1, col_tile)
    var = var_ref[0]
    g = gamma_ref[0]
    b = beta_ref[0]
    y = (x - mu) * jax.lax.rsqrt(var + eps) * g + b
    y_ref[0] = y.astype(y_ref.dtype)


def _bwd_stats_kernel(x_ref, dy_ref, mu_ref, rstd_ref, sdy_ref, sdyxh_ref):
    """Accumulate sum_r dy and sum_r dy*xhat per (ghost, col-tile).

    Same grid as the forward reduction; row-padding needs no mask because the
    padded dy rows are zero and multiply every term.
    """
    r = pl.program_id(2)

    @pl.when(r == 0)
    def _init():
        sdy_ref[...] = jnp.zeros_like(sdy_ref)
        sdyxh_ref[...] = jnp.zeros_like(sdyxh_ref)

    x = x_ref[0].astype(jnp.float32)                  # (row_tile, col_tile)
    dy = dy_ref[0].astype(jnp.float32)
    xhat = (x - mu_ref[0]) * rstd_ref[0]
    sdy_ref[0] += jnp.sum(dy, axis=0, keepdims=True)
    sdyxh_ref[0] += jnp.sum(dy * xhat, axis=0, keepdims=True)


def _bwd_dx_kernel(x_ref, dy_ref, mu_ref, c1_ref, c2_ref, c3_ref, dx_ref):
    """Elementwise dx = dy*c1 + (x - mu)*c2 + c3 with per-(ghost, channel)
    coefficients (c1 = gamma*rstd, c2 = 2*gvar/R, c3 = gmu/R)."""
    x = x_ref[0].astype(jnp.float32)
    dy = dy_ref[0].astype(jnp.float32)
    dx = dy * c1_ref[0] + (x - mu_ref[0]) * c2_ref[0] + c3_ref[0]
    dx_ref[0] = dx.astype(dx_ref.dtype)


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


class _Tiling(NamedTuple):
    """How a (G, R, C) input maps onto the kernels' (G, Rp, Cp) grid."""
    fold: int        # rows folded into one lane row (1: no fold)
    rows: int        # R // fold, before row padding
    row_tile: int
    col_tile: int
    cp: int          # padded lane width


def _tiling(R: int, C: int, row_tile: int, col_tile: int) -> _Tiling:
    fold = col_tile // C if (C < col_tile and col_tile % C == 0
                             and R % (col_tile // C) == 0) else 1
    rows, width = R // fold, C * fold
    # a lane axis narrower than one tile rides whole (block == full dim)
    ct = min(col_tile, width)
    rt = min(row_tile, -(-rows // 8) * 8)
    return _Tiling(fold, rows, rt, ct, -(-width // ct) * ct)


def _grid_in(t: _Tiling, x: jax.Array) -> jax.Array:
    """(G, R, C) -> the padded, lane-folded (G, Rp, Cp) kernel operand."""
    G, R, C = x.shape
    x = x.reshape(G, t.rows, C * t.fold)
    return _pad_to(_pad_to(x, 2, t.cp), 1, t.row_tile)


def _grid_out(t: _Tiling, y: jax.Array, R: int, C: int) -> jax.Array:
    return y[:, :t.rows, :C * t.fold].reshape(y.shape[0], R, C)


def _to_lanes(t: _Tiling, a: jax.Array) -> jax.Array:
    """Per-channel (G, C) -> per-lane (G, 1, Cp) f32 (lane l is channel
    l % C; padded lanes are zero)."""
    a = jnp.tile(a.astype(jnp.float32), (1, t.fold))
    return _pad_to(a, 1, t.cp)[:, None, :]


def _from_lanes(t: _Tiling, a: jax.Array, C: int) -> jax.Array:
    """Per-lane (G, 1, Cp) sums -> per-channel (G, C)."""
    return a[:, 0, :C * t.fold].reshape(a.shape[0], t.fold, C).sum(axis=1)


def gbn_forward_pallas(xg: jax.Array, gamma: jax.Array, beta: jax.Array, *,
                       eps: float = 1e-5,
                       row_tile: int = DEFAULT_ROW_TILE,
                       col_tile: int = DEFAULT_COL_TILE,
                       interpret: bool = False
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """xg: (G, R, C) -> (y (G,R,C), mu (G,C), var (G,C))."""
    G, R, C = xg.shape
    t = _tiling(R, C, row_tile, col_tile)
    xp = _grid_in(t, xg)
    rt, ct = t.row_tile, t.col_tile
    nr, nc = xp.shape[1] // rt, t.cp // ct
    x_spec = pl.BlockSpec((1, rt, ct), lambda g, c, r: (g, r, c))
    stat_spec = pl.BlockSpec((1, 1, ct), lambda g, c, r: (g, 0, c))
    param_spec = pl.BlockSpec((1, 1, ct), lambda g, c, r: (0, 0, c))

    sums, sqs = pl.pallas_call(
        functools.partial(_stats_kernel, n_rows=t.rows),
        name="gbn_fwd_stats",
        grid=(G, nc, nr),
        in_specs=[x_spec],
        out_specs=[stat_spec, stat_spec],
        out_shape=[jax.ShapeDtypeStruct((G, 1, t.cp), jnp.float32)] * 2,
        interpret=interpret,
    )(xp)
    mu = _from_lanes(t, sums, C) / R
    var = _from_lanes(t, sqs, C) / R - mu * mu

    y = pl.pallas_call(
        functools.partial(_normalize_kernel, eps=eps),
        name="gbn_fwd_normalize",
        grid=(G, nc, nr),
        in_specs=[x_spec, stat_spec, stat_spec, param_spec, param_spec],
        out_specs=x_spec,
        out_shape=jax.ShapeDtypeStruct(xp.shape, xg.dtype),
        interpret=interpret,
    )(xp, _to_lanes(t, mu), _to_lanes(t, var),
      _to_lanes(t, gamma.reshape(1, C)), _to_lanes(t, beta.reshape(1, C)))
    return _grid_out(t, y, R, C), mu, var


def gbn_backward_pallas(xg: jax.Array, gamma: jax.Array, mu: jax.Array,
                        var: jax.Array, dy: jax.Array, dmu: jax.Array,
                        dvar: jax.Array, *, eps: float = 1e-5,
                        row_tile: int = DEFAULT_ROW_TILE,
                        col_tile: int = DEFAULT_COL_TILE,
                        interpret: bool = False
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """VJP of :func:`gbn_forward_pallas` w.r.t. (xg, gamma, beta).

    xg, dy: (G, R, C); mu, var, dmu, dvar: (G, C) — the saved forward
    statistics and the cotangents of all three forward outputs.
    Returns (dx (G, R, C) in xg.dtype, dgamma (C,), dbeta (C,)) — the
    parameter grads in float32.
    """
    G, R, C = xg.shape
    t = _tiling(R, C, row_tile, col_tile)
    xp = _grid_in(t, xg)
    dyp = _grid_in(t, dy)
    rt, ct = t.row_tile, t.col_tile
    nr, nc = xp.shape[1] // rt, t.cp // ct
    x_spec = pl.BlockSpec((1, rt, ct), lambda g, c, r: (g, r, c))
    stat_spec = pl.BlockSpec((1, 1, ct), lambda g, c, r: (g, 0, c))

    mu = mu.astype(jnp.float32)
    rstd = jax.lax.rsqrt(var.astype(jnp.float32) + eps)          # (G, C)
    mul = _to_lanes(t, mu)
    sdy, sdyxh = pl.pallas_call(
        _bwd_stats_kernel,
        name="gbn_bwd_stats",
        grid=(G, nc, nr),
        in_specs=[x_spec, x_spec, stat_spec, stat_spec],
        out_specs=[stat_spec, stat_spec],
        out_shape=[jax.ShapeDtypeStruct((G, 1, t.cp), jnp.float32)] * 2,
        interpret=interpret,
    )(xp, dyp, mul, _to_lanes(t, rstd))
    sdy, sdyxh = _from_lanes(t, sdy, C), _from_lanes(t, sdyxh, C)

    # (G, C)-sized glue: fold the tile sums and the upstream mu/var
    # cotangents into per-(ghost, channel) dx coefficients. With
    # mu = mean(x) the explicit dvar/dmu cross term vanishes identically.
    g32 = gamma.astype(jnp.float32).reshape(1, C)
    gvar = dvar.astype(jnp.float32) - 0.5 * g32 * rstd * rstd * sdyxh
    gmu = dmu.astype(jnp.float32) - g32 * rstd * sdy
    c1 = g32 * rstd
    c2 = 2.0 * gvar / R
    c3 = gmu / R

    dx = pl.pallas_call(
        _bwd_dx_kernel,
        name="gbn_bwd_dx",
        grid=(G, nc, nr),
        in_specs=[x_spec, x_spec] + [stat_spec] * 4,
        out_specs=x_spec,
        out_shape=jax.ShapeDtypeStruct(xp.shape, xg.dtype),
        interpret=interpret,
    )(xp, dyp, mul, _to_lanes(t, c1), _to_lanes(t, c2), _to_lanes(t, c3))

    return _grid_out(t, dx, R, C), jnp.sum(sdyxh, axis=0), jnp.sum(sdy, axis=0)
