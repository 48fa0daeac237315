"""Pallas TPU kernels for one chunk of the Mamba selective scan
(forward + dedicated backward).

TPU adaptation of the CUDA selective-scan: instead of a warp-parallel scan
over the sequence, the kernel keeps the (d_inner-tile, d_state) hidden state
resident in VMEM and walks the chunk sequentially with a ``fori_loop`` —
sequential-over-time, parallel-over-channels, which matches the VPU's
(8, 128) lanes (channels on the lane axis). The outer grid parallelises over
(batch, d_inner tiles); chunk boundaries are handled by the carried h.

Backward (:func:`mamba_chunk_backward_pallas`): same (batch, d_inner-tile)
grid. Phase 1 re-runs the forward recurrence inside the kernel, stashing the
per-step states h_t in a (chunk, dit, ds) VMEM scratch (recompute-in-VMEM:
the (B, c, di, ds) state trajectory never exists in HBM). Phase 2 walks the
chunk in REVERSE with a ``fori_loop`` carrying the state cotangent dh,
emitting dx/ddt per (time, d-tile), accumulating dB/dC across d-tiles in the
output block (d-tile is the innermost grid axis), and dA in the loop carry.
The VMEM working set is ``chunk * dit * ds`` floats — callers bound it by
choosing ``di_tile`` (and the model's chunk size) accordingly.

Public entry: :func:`repro.kernels.ops.mamba_chunk` (differentiable —
``jax.custom_vjp`` pairs the two kernels, with no oracle forward replay).
Oracle: :func:`repro.kernels.ref.mamba_chunk_ref` /
:func:`repro.kernels.ref.mamba_chunk_vjp_ref`.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_DI_TILE = 512


def _mamba_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, h0_ref,
                  y_ref, hout_ref, *, chunk: int):
    """Blocks: x/dt (1, chunk, dit); b/c (1, chunk, ds); a (dit, ds);
    h0/hout (1, dit, ds); y (1, chunk, dit)."""
    a = a_ref[...].astype(jnp.float32)                  # (dit, ds)
    h = h0_ref[0].astype(jnp.float32)                   # (dit, ds)

    def step(t, h):
        x_t = x_ref[0, t].astype(jnp.float32)           # (dit,)
        dt_t = dt_ref[0, t].astype(jnp.float32)
        b_t = b_ref[0, t].astype(jnp.float32)           # (ds,)
        c_t = c_ref[0, t].astype(jnp.float32)
        decay = jnp.exp(dt_t[:, None] * a)              # (dit, ds)
        h = decay * h + (dt_t * x_t)[:, None] * b_t[None, :]
        y_ref[0, t] = jnp.sum(h * c_t[None, :], axis=-1).astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h)
    hout_ref[0] = h.astype(hout_ref.dtype)


def mamba_chunk_pallas(xc: jax.Array, dt: jax.Array, Bm: jax.Array,
                       Cm: jax.Array, A: jax.Array, h0: jax.Array, *,
                       di_tile: int = DEFAULT_DI_TILE,
                       interpret: bool = False
                       ) -> Tuple[jax.Array, jax.Array]:
    """xc, dt: (B, c, di); Bm, Cm: (B, c, ds); A: (di, ds); h0: (B, di, ds).

    Returns (y (B, c, di) f32, h_last (B, di, ds) f32).
    """
    B, c, di = xc.shape
    ds = A.shape[1]
    dit = min(di_tile, di)
    assert di % dit == 0, (di, dit)
    grid = (B, di // dit)

    y, hout = pl.pallas_call(
        functools.partial(_mamba_kernel, chunk=c),
        name="mamba_scan_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, c, dit), lambda b, d: (b, 0, d)),
            pl.BlockSpec((1, c, dit), lambda b, d: (b, 0, d)),
            pl.BlockSpec((1, c, ds), lambda b, d: (b, 0, 0)),
            pl.BlockSpec((1, c, ds), lambda b, d: (b, 0, 0)),
            pl.BlockSpec((dit, ds), lambda b, d: (d, 0)),
            pl.BlockSpec((1, dit, ds), lambda b, d: (b, d, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, c, dit), lambda b, d: (b, 0, d)),
            pl.BlockSpec((1, dit, ds), lambda b, d: (b, d, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, c, di), jnp.float32),
            jax.ShapeDtypeStruct((B, di, ds), jnp.float32),
        ],
        interpret=interpret,
    )(xc, dt, Bm, Cm, A, h0)
    return y, hout


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _mamba_bwd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, h0_ref, dy_ref,
                      dhl_ref, dx_ref, ddt_ref, db_ref, dc_ref, da_ref,
                      dh0_ref, hs_ref, *, chunk: int):
    """Blocks: x/dt/dy/dx/ddt (1, chunk, dit); b/c/db/dc (1, chunk, ds);
    a (dit, ds); h0/dh0/dhl/da (1, dit, ds); hs scratch (chunk, dit, ds).

    db/dc accumulate across the (innermost) d-tile grid axis; da is summed
    over the batch axis by the caller.
    """
    d = pl.program_id(1)
    a = a_ref[...].astype(jnp.float32)                  # (dit, ds)
    h0 = h0_ref[0].astype(jnp.float32)

    # phase 1: recompute the forward states of this chunk into VMEM scratch
    def fwd_step(t, h):
        x_t = x_ref[0, t].astype(jnp.float32)           # (dit,)
        dt_t = dt_ref[0, t].astype(jnp.float32)
        b_t = b_ref[0, t].astype(jnp.float32)           # (ds,)
        decay = jnp.exp(dt_t[:, None] * a)              # (dit, ds)
        h = decay * h + (dt_t * x_t)[:, None] * b_t[None, :]
        hs_ref[t] = h
        return h

    jax.lax.fori_loop(0, chunk, fwd_step, h0)

    @pl.when(d == 0)
    def _init():
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    # phase 2: reverse-time sweep carrying (dh, dA accumulator)
    def bwd_step(i, carry):
        t = chunk - 1 - i
        dh, da = carry
        x_t = x_ref[0, t].astype(jnp.float32)
        dt_t = dt_ref[0, t].astype(jnp.float32)
        b_t = b_ref[0, t].astype(jnp.float32)
        c_t = c_ref[0, t].astype(jnp.float32)
        dy_t = dy_ref[0, t].astype(jnp.float32)         # (dit,)
        h_t = hs_ref[t]                                 # (dit, ds)
        h_prev = jnp.where(t == 0, h0, hs_ref[jnp.maximum(t - 1, 0)])
        # total cotangent of h_t: carried from t+1 plus y_t's contribution
        g = dh + dy_t[:, None] * c_t[None, :]
        dc_ref[0, t] += jnp.sum(h_t * dy_t[:, None], axis=0)       # (ds,)
        decay = jnp.exp(dt_t[:, None] * a)
        # cotangent of the exponent u = dt_t * A (d exp(u)/du = exp(u))
        du = g * h_prev * decay
        da = da + du * dt_t[:, None]
        gb = jnp.sum(g * b_t[None, :], axis=1)          # (dit,) = d(dt*x)
        db_ref[0, t] += jnp.sum(g * (dt_t * x_t)[:, None], axis=0)
        dx_ref[0, t] = (dt_t * gb).astype(dx_ref.dtype)
        ddt_ref[0, t] = (jnp.sum(du * a, axis=1)
                         + x_t * gb).astype(ddt_ref.dtype)
        return g * decay, da

    dh, da = jax.lax.fori_loop(
        0, chunk, bwd_step,
        (dhl_ref[0].astype(jnp.float32), jnp.zeros_like(a)))
    dh0_ref[0] = dh.astype(dh0_ref.dtype)
    da_ref[0] = da.astype(da_ref.dtype)


def mamba_chunk_backward_pallas(xc: jax.Array, dt: jax.Array, Bm: jax.Array,
                                Cm: jax.Array, A: jax.Array, h0: jax.Array,
                                dy: jax.Array, dh_last: jax.Array, *,
                                di_tile: int = DEFAULT_DI_TILE,
                                interpret: bool = False
                                ) -> Tuple[jax.Array, ...]:
    """VJP of :func:`mamba_chunk_pallas` w.r.t. all six inputs.

    Shapes as the forward, plus the output cotangents dy (B, c, di) and
    dh_last (B, di, ds). Returns (dxc, ddt, dB, dC, dA, dh0) — dxc/ddt/dB/dC
    in the corresponding input dtypes, dA/dh0 in f32.
    """
    B, c, di = xc.shape
    ds = A.shape[1]
    dit = min(di_tile, di)
    assert di % dit == 0, (di, dit)
    grid = (B, di // dit)

    dxc, ddt, dB, dC, dA_b, dh0 = pl.pallas_call(
        functools.partial(_mamba_bwd_kernel, chunk=c),
        name="mamba_scan_bwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, c, dit), lambda b, d: (b, 0, d)),
            pl.BlockSpec((1, c, dit), lambda b, d: (b, 0, d)),
            pl.BlockSpec((1, c, ds), lambda b, d: (b, 0, 0)),
            pl.BlockSpec((1, c, ds), lambda b, d: (b, 0, 0)),
            pl.BlockSpec((dit, ds), lambda b, d: (d, 0)),
            pl.BlockSpec((1, dit, ds), lambda b, d: (b, d, 0)),
            pl.BlockSpec((1, c, dit), lambda b, d: (b, 0, d)),
            pl.BlockSpec((1, dit, ds), lambda b, d: (b, d, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, c, dit), lambda b, d: (b, 0, d)),
            pl.BlockSpec((1, c, dit), lambda b, d: (b, 0, d)),
            pl.BlockSpec((1, c, ds), lambda b, d: (b, 0, 0)),
            pl.BlockSpec((1, c, ds), lambda b, d: (b, 0, 0)),
            pl.BlockSpec((1, dit, ds), lambda b, d: (b, d, 0)),
            pl.BlockSpec((1, dit, ds), lambda b, d: (b, d, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, c, di), xc.dtype),
            jax.ShapeDtypeStruct((B, c, di), dt.dtype),
            jax.ShapeDtypeStruct((B, c, ds), jnp.float32),
            jax.ShapeDtypeStruct((B, c, ds), jnp.float32),
            jax.ShapeDtypeStruct((B, di, ds), jnp.float32),
            jax.ShapeDtypeStruct((B, di, ds), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((c, dit, ds), jnp.float32)],
        interpret=interpret,
    )(xc, dt, Bm, Cm, A, h0, dy, dh_last)
    # dA sums the per-batch blocks (each (b, d) grid cell owns one slice)
    return (dxc, ddt, dB.astype(Bm.dtype), dC.astype(Cm.dtype),
            dA_b.sum(axis=0).astype(A.dtype), dh0.astype(h0.dtype))
