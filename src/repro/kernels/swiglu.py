"""Pallas TPU kernel for fused SwiGLU: ``h = silu(x @ wg) * (x @ wu)``.

The unfused MLP front half runs three passes (gate GEMM, up GEMM,
elementwise gate) and materialises both (N, F) hidden activations in HBM.
This kernel fuses all three: each ``(row_tile, d)`` x block is read once
per hidden tile, both GEMM partials and the silu-gate product happen in
VMEM, and only ``h`` plus ONE hidden residual — the pre-activation gate
``g = x @ wg`` — are written out (``u = x @ wu`` is recomputed by the
backward, never stored).

Layout: rows (B*T) tiled on the sublane axis, ``d_model`` whole on the
lane/contraction axis, the hidden axis F tiled in 128-multiples
(``ops._fused_tile`` gates both widths; non-aligned dims fall back to the
jnp oracle with a one-time warning).

Backward (`swiglu_backward_pallas`), grid (rows, hidden-tiles) with the
hidden axis innermost: recompute ``u`` in-kernel, form the elementwise
chain (``sig = sigmoid(g)``)

    du = dh * g * sig
    dg = dh * u * sig * (1 + g * (1 - sig))

emit ``dg``/``du`` tiles, and accumulate ``dx = dg @ wg^T + du @ wu^T``
across hidden tiles directly in an f32 ``(row_tile, d)`` output block
whose index map is constant over the inner grid axis (the GBN
consecutive-revisit pattern). The weight grads are two plain GEMMs
outside the kernel (``dwg = x^T @ dg``, ``dwu = x^T @ du``) — they need
the full dg/du tiles anyway, so there is nothing to fuse.

Public entry: :func:`repro.kernels.ops.swiglu` (custom_vjp). Oracle:
:func:`repro.kernels.ref.swiglu_ref`.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_ROW_TILE = 128


def _pad_rows(x: jax.Array, mult: int) -> jax.Array:
    pad = (-x.shape[0]) % mult
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


# the backward holds a (row_tile, d) f32 dx block and four (d, tile)
# weight buffers at once: at d = 2048 that is past v5e's 16 MiB default
# scoped VMEM, so it asks for more (of the 128 MiB the core has)
_BWD_VMEM_LIMIT = 64 * 1024 * 1024


def _f_tile(F: int, cap: int = 512) -> int:
    """Largest standard hidden tile (<= cap) dividing F (F is 128-aligned
    here)."""
    for t in (512, 384, 256, 128):
        if t <= cap and F % t == 0:
            return t
    raise ValueError(f"hidden dim {F} is not 128-aligned")


def _dot(a, b):
    # operands in their stored dtype (bf16 x bf16 products are exact in
    # f32), accumulated in f32 — no f32 copy of a weight tile in VMEM
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _dot_t(a, b):
    """a @ b.T without materialising the transpose."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _fwd_kernel(x_ref, wg_ref, wu_ref, h_ref, g_ref):
    x = x_ref[...]
    g = _dot(x, wg_ref[...])
    u = _dot(x, wu_ref[...])
    g_ref[...] = g.astype(g_ref.dtype)
    h_ref[...] = (g * jax.nn.sigmoid(g) * u).astype(h_ref.dtype)


def _bwd_kernel(x_ref, wg_ref, wu_ref, g_ref, dh_ref, dg_ref, du_ref,
                dx_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dx_ref[...] = jnp.zeros_like(dx_ref)

    wg = wg_ref[...]
    wu = wu_ref[...]
    g = g_ref[...].astype(jnp.float32)
    dh = dh_ref[...].astype(jnp.float32)
    u = _dot(x_ref[...], wu)                    # recompute — u is not saved
    sig = jax.nn.sigmoid(g)
    du = (dh * g * sig).astype(du_ref.dtype)
    dg = (dh * u * sig * (1.0 + g * (1.0 - sig))).astype(dg_ref.dtype)
    dg_ref[...] = dg
    du_ref[...] = du
    # dx from the stored-dtype dg/du — the same values the weight-grad
    # GEMMs outside the kernel consume
    dx_ref[...] += (_dot_t(dg.astype(wg.dtype), wg)
                    + _dot_t(du.astype(wu.dtype), wu))


def swiglu_pallas(x: jax.Array, wg: jax.Array, wu: jax.Array, *,
                  row_tile: int = DEFAULT_ROW_TILE,
                  interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """x: (N, d); wg, wu: (d, F); d and F 128-multiples.

    Returns (h = silu(x @ wg) * (x @ wu), g = x @ wg), both (N, F) in
    x.dtype.
    """
    N, d = x.shape
    F = wg.shape[1]
    bf = _f_tile(F)
    xp = _pad_rows(x, row_tile)
    nr, nf = xp.shape[0] // row_tile, F // bf
    out_spec = pl.BlockSpec((row_tile, bf), lambda i, j: (i, j))
    h, g = pl.pallas_call(
        _fwd_kernel,
        grid=(nr, nf),
        in_specs=[pl.BlockSpec((row_tile, d), lambda i, j: (i, 0)),
                  pl.BlockSpec((d, bf), lambda i, j: (0, j)),
                  pl.BlockSpec((d, bf), lambda i, j: (0, j))],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((xp.shape[0], F), x.dtype),
                   jax.ShapeDtypeStruct((xp.shape[0], F), x.dtype)],
        interpret=interpret,
    )(xp, wg, wu)
    return h[:N], g[:N]


def swiglu_backward_pallas(x: jax.Array, wg: jax.Array, wu: jax.Array,
                           g: jax.Array, dh: jax.Array, *,
                           row_tile: int = DEFAULT_ROW_TILE,
                           interpret: bool = False
                           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Activation-side VJP of :func:`swiglu_pallas` from the saved gate
    ``g``. Returns (dx (N, d) f32, dg (N, F), du (N, F)); the caller forms
    ``dwg = x^T @ dg`` / ``dwu = x^T @ du`` outside (plain GEMMs).
    """
    N, d = x.shape
    F = wg.shape[1]
    bf = _f_tile(F, cap=256)
    xp = _pad_rows(x, row_tile)
    gp = _pad_rows(g, row_tile)
    dhp = _pad_rows(dh, row_tile)
    nr, nf = xp.shape[0] // row_tile, F // bf
    hid_spec = pl.BlockSpec((row_tile, bf), lambda i, j: (i, j))
    dg, du, dx = pl.pallas_call(
        _bwd_kernel,
        grid=(nr, nf),
        in_specs=[pl.BlockSpec((row_tile, d), lambda i, j: (i, 0)),
                  pl.BlockSpec((d, bf), lambda i, j: (0, j)),
                  pl.BlockSpec((d, bf), lambda i, j: (0, j)),
                  hid_spec, hid_spec],
        out_specs=[hid_spec, hid_spec,
                   pl.BlockSpec((row_tile, d), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((xp.shape[0], F), x.dtype),
                   jax.ShapeDtypeStruct((xp.shape[0], F), x.dtype),
                   jax.ShapeDtypeStruct((xp.shape[0], d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_BWD_VMEM_LIMIT),
        interpret=interpret,
    )(xp, wg, wu, gp, dhp)
    return dx[:N], dg[:N], du[:N]
