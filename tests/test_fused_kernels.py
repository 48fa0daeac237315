"""Fused row/attention kernels and the int8 paged KV cache.

Covers the PR 9 widening: fused rmsnorm+residual, fused SwiGLU and
RoPE-fused flash attention — forward AND grad against the jnp oracles in
f32 and bf16 (the Pallas pair driven explicitly with ``interpret=True``;
off-TPU the ops entries dispatch to the fused jnp lowering) — plus the
fused dkv+dq flash backward, the ``_fused_tile`` oracle fallback, and the
int8 page pool: per-slot quantize/dequant bounds, in-kernel dequant vs the
dequantizing oracle, trash-page no-op on quantized pages, and engine-level
greedy parity vs the full-precision pool."""
import dataclasses
import warnings as warnings_mod

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import (flash_attention_backward_pallas,
                                           flash_attention_pallas,
                                           flash_attention_rope_backward_pallas,
                                           flash_attention_rope_pallas)
from repro.kernels.flash_decode import (flash_decode_paged_blockwise,
                                        flash_decode_paged_pallas)
from repro.kernels.fused_norm import (rmsnorm_residual_backward_pallas,
                                      rmsnorm_residual_pallas)
from repro.kernels.swiglu import swiglu_backward_pallas, swiglu_pallas

pytestmark = pytest.mark.tier1


# ---------------------------------------------------------------------------
# fused rmsnorm + residual
# ---------------------------------------------------------------------------

NORM_SHAPES = [(17, 128), (64, 256), (5, 512)]


@pytest.mark.parametrize("shape", NORM_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_residual_pallas_vs_ref(shape, dtype):
    N, d = shape
    rng = jax.random.PRNGKey(N + d)
    x = jax.random.normal(rng, shape, jnp.float32).astype(dtype)
    r = jax.random.normal(jax.random.fold_in(rng, 1), shape,
                          jnp.float32).astype(dtype)
    scale = jnp.linspace(0.5, 1.5, d)
    y, s = rmsnorm_residual_pallas(x, r, scale, interpret=True)
    yr, sr = ref.rmsnorm_residual_ref(x, r, scale)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(s, np.float32),
                               np.asarray(sr, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_residual_backward_pallas_vs_oracle(dtype):
    """Backward kernel from the saved (s, scale) == oracle VJP (which also
    certifies dr == dx: the residual add fans the cotangent out equally)."""
    N, d = 33, 256
    rng = jax.random.PRNGKey(7)
    x = jax.random.normal(rng, (N, d), jnp.float32).astype(dtype)
    r = jax.random.normal(jax.random.fold_in(rng, 1), (N, d),
                          jnp.float32).astype(dtype)
    scale = jnp.linspace(0.5, 1.5, d)
    dy = jax.random.normal(jax.random.fold_in(rng, 2), (N, d),
                           jnp.float32).astype(dtype)
    ds = jax.random.normal(jax.random.fold_in(rng, 3), (N, d),
                           jnp.float32).astype(dtype)
    s = x + r
    dx, dscale = rmsnorm_residual_backward_pallas(s, scale, dy, ds,
                                                  interpret=True)
    dxr, drr, dscr = ref.rmsnorm_residual_vjp_ref(x, r, scale, (dy, ds))
    tol = 1e-5 if dtype == jnp.float32 else 1e-1
    np.testing.assert_allclose(np.asarray(dxr, np.float32),
                               np.asarray(drr, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(dx, np.float32),
                               np.asarray(dxr, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(dscale), np.asarray(dscr, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_residual_grad_vs_oracle(dtype):
    """jax.grad through ops.rmsnorm_residual == jax.grad through the oracle
    with live cotangents on BOTH outputs (y and the new residual stream)."""
    N, d = 20, 256
    rng = jax.random.PRNGKey(11)
    x = jax.random.normal(rng, (N, d), jnp.float32).astype(dtype)
    r = jax.random.normal(jax.random.fold_in(rng, 1), (N, d),
                          jnp.float32).astype(dtype)
    scale = jnp.linspace(0.5, 1.5, d)
    wy = jax.random.normal(jax.random.fold_in(rng, 2), (N, d))
    ws = jax.random.normal(jax.random.fold_in(rng, 3), (N, d))

    def make_loss(f):
        def loss(a, b, c):
            y, s = f(a, b, c)
            return ((y.astype(jnp.float32) * wy).sum()
                    + (s.astype(jnp.float32) * ws).sum())
        return loss

    gk = jax.grad(make_loss(ops.rmsnorm_residual), argnums=(0, 1, 2))(
        x, r, scale)
    gr = jax.grad(make_loss(ref.rmsnorm_residual_ref), argnums=(0, 1, 2))(
        x, r, scale)
    tol = 1e-5 if dtype == jnp.float32 else 1e-1
    for got, want in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# fused SwiGLU
# ---------------------------------------------------------------------------

SWIGLU_SHAPES = [(9, 128, 256), (33, 256, 384)]


@pytest.mark.parametrize("shape", SWIGLU_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_swiglu_pallas_vs_ref(shape, dtype):
    N, d, F = shape
    rng = jax.random.PRNGKey(sum(shape))
    x = jax.random.normal(rng, (N, d), jnp.float32).astype(dtype)
    wg = (jax.random.normal(jax.random.fold_in(rng, 1), (d, F))
          / d ** 0.5).astype(dtype)
    wu = (jax.random.normal(jax.random.fold_in(rng, 2), (d, F))
          / d ** 0.5).astype(dtype)
    h, g = swiglu_pallas(x, wg, wu, interpret=True)
    hr, gr = ref.swiglu_ref(x, wg, wu)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(h, np.float32),
                               np.asarray(hr, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(g, np.float32),
                               np.asarray(gr, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_swiglu_backward_pallas_vs_oracle(dtype):
    """Activation-side backward kernel (dx from the saved gate g, dg/du for
    the outside weight GEMMs) == oracle VJP."""
    N, d, F = 17, 128, 256
    rng = jax.random.PRNGKey(13)
    x = jax.random.normal(rng, (N, d), jnp.float32).astype(dtype)
    wg = (jax.random.normal(jax.random.fold_in(rng, 1), (d, F))
          / d ** 0.5).astype(dtype)
    wu = (jax.random.normal(jax.random.fold_in(rng, 2), (d, F))
          / d ** 0.5).astype(dtype)
    dh = jax.random.normal(jax.random.fold_in(rng, 3), (N, F),
                           jnp.float32).astype(dtype)
    _, g = ref.swiglu_ref(x, wg, wu)
    dx, dg, du = swiglu_backward_pallas(x, wg, wu, g, dh, interpret=True)
    dwg = jnp.dot(x.T.astype(jnp.float32), dg.astype(jnp.float32))
    dwu = jnp.dot(x.T.astype(jnp.float32), du.astype(jnp.float32))
    dxr, dwgr, dwur = ref.swiglu_vjp_ref(x, wg, wu, dh)
    tol = 1e-4 if dtype == jnp.float32 else 1e-1
    for got, want in ((dx, dxr), (dwg, dwgr), (dwu, dwur)):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_swiglu_grad_vs_oracle(dtype):
    """jax.grad through ops.swiglu == jax.grad through the oracle for all
    three inputs (x, wg, wu)."""
    N, d, F = 12, 128, 256
    rng = jax.random.PRNGKey(17)
    x = jax.random.normal(rng, (N, d), jnp.float32).astype(dtype)
    wg = (jax.random.normal(jax.random.fold_in(rng, 1), (d, F))
          / d ** 0.5).astype(dtype)
    wu = (jax.random.normal(jax.random.fold_in(rng, 2), (d, F))
          / d ** 0.5).astype(dtype)
    w = jax.random.normal(jax.random.fold_in(rng, 3), (N, F))

    def make_loss(f):
        return lambda a, b, c: (f(a, b, c).astype(jnp.float32) * w).sum()

    gk = jax.grad(make_loss(ops.swiglu), argnums=(0, 1, 2))(x, wg, wu)
    gr = jax.grad(make_loss(lambda a, b, c: ref.swiglu_ref(a, b, c)[0]),
                  argnums=(0, 1, 2))(x, wg, wu)
    tol = 1e-4 if dtype == jnp.float32 else 1e-1
    for got, want in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# RoPE-fused flash attention
# ---------------------------------------------------------------------------

# the qwen3 training cell's head dim and base at the last 148 positions of a
# 2048-token row, where its rotation angles are largest
CELL_ANGLES = (1, 2, 1, 148, 128)

ROPE_SHAPES = [
    # (B, H, KV, T, hd) — self-attention: S == T
    (1, 2, 2, 17, 32),
    (2, 4, 2, 64, 64),
    CELL_ANGLES,
]


def _rope_inputs(shape, dtype, salt=0, first_pos=0):
    B, H, KV, T, hd = shape
    rng = jax.random.PRNGKey((sum(shape) + salt) % 2 ** 31)
    q = jax.random.normal(rng, (B, H, T, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, KV, T, hd),
                          jnp.float32).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(rng, 2), (B, KV, T, hd),
                          jnp.float32).astype(dtype)
    # staggered per-row positions (continuation offsets, not just 0..T-1)
    pos = (first_pos + jnp.arange(T)[None, :]
           + 3 * jnp.arange(B)[:, None]).astype(jnp.float32)
    return q, k, v, pos


@pytest.mark.parametrize("shape", ROPE_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [None, 13])
def test_flash_attention_rope_vs_ref(shape, dtype, window):
    """Rotate-then-attend kernel path == rope-then-attend oracle
    composition, up to the cell's angles (theta 1e6, positions
    1900-2047)."""
    theta, first = (1e6, 1900) if shape == CELL_ANGLES else (1e4, 0)
    q, k, v, pos = _rope_inputs(shape, dtype, first_pos=first)
    out = flash_attention_rope_pallas(q, k, v, pos, theta=theta, causal=True,
                                      window=window, block_q=32, block_k=32,
                                      interpret=True)
    want = ref.attention_rope_ref(q, k, v, pos, theta=theta, causal=True,
                                  window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    if shape == CELL_ANGLES and dtype == jnp.float32:
        # an f32 angle near 2047 rad is rounded by up to 1.2e-4 rad, in the
        # oracle's frequencies as in the kernel path's: each reads up to
        # 4.1e-5 from a float64 rotation here. Rotated q/k rounded to bf16
        # would miss by 4.4e-3.
        tol = 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_rope_backward_vs_oracle(dtype):
    """Rope backward (un-rotate dq/dk around the shared non-rope kernels)
    fed the forward kernel's own residuals == oracle VJP."""
    q, k, v, pos = _rope_inputs((2, 4, 2, 33, 32), dtype, salt=5)
    do = jax.random.normal(jax.random.PRNGKey(6), q.shape,
                           jnp.float32).astype(dtype)
    o, lse = flash_attention_rope_pallas(q, k, v, pos, theta=1e4,
                                         causal=True, block_q=32, block_k=32,
                                         return_residuals=True,
                                         interpret=True)
    dq, dk, dv = flash_attention_rope_backward_pallas(
        q, k, v, pos, o, lse, do, theta=1e4, causal=True, block_q=32,
        block_k=32, interpret=True)
    want = ref.attention_rope_vjp_ref(q, k, v, pos, do, theta=1e4,
                                      causal=True)
    tol = 5e-4 if dtype == jnp.float32 else 2e-1
    for got, wnt in zip((dq, dk, dv), want):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(wnt, np.float32),
                                   rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_rope_grad_vs_oracle(dtype):
    """jax.grad through the ops.flash_attention_rope custom_vjp (model
    layout, unrotated q/k in) == jax.grad through the oracle composition."""
    B, H, KV, T, hd = 2, 4, 2, 20, 32
    rng = jax.random.PRNGKey(23)
    q = jax.random.normal(rng, (B, T, H, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, T, KV, hd),
                          jnp.float32).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(rng, 2), (B, T, KV, hd),
                          jnp.float32).astype(dtype)
    w = jax.random.normal(jax.random.fold_in(rng, 3), (B, T, H, hd))
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))

    def make_loss(f):
        return lambda a, b, c: (f(a, b, c).astype(jnp.float32) * w).sum()

    gk = jax.grad(make_loss(lambda a, b, c: ops.flash_attention_rope(
        a, b, c, pos, theta=1e4)), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(make_loss(lambda a, b, c: ref.attention_rope_ref(
        a.swapaxes(1, 2), b.swapaxes(1, 2), c.swapaxes(1, 2), pos,
        theta=1e4).swapaxes(1, 2)), argnums=(0, 1, 2))(q, k, v)
    tol = 5e-4 if dtype == jnp.float32 else 2e-1
    for got, want in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in its
    equations, leaving out the kernels of ``pallas_call``s."""
    from jax.extend import core as jcore
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for val in eqn.params.values():
            for sub in val if isinstance(val, (tuple, list)) else (val,):
                if isinstance(sub, jcore.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jcore.Jaxpr):
                    yield from _eqns(sub)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_rope_forward_is_one_kernel_without_sin_cos(dtype):
    """One ops.flash_attention_rope forward holds exactly one pallas_call,
    ``flash_attention_rope_fwd`` (the roofline reader counts it), and the
    rotation's sin/cos run outside it: none per (q block, kv block) pair."""
    B, H, KV, T, hd = 1, 2, 1, 40, 32
    q = jnp.ones((B, T, H, hd), dtype)
    kv = jnp.ones((B, T, KV, hd), dtype)
    pos = jnp.arange(T)[None]
    jaxpr = jax.make_jaxpr(lambda a, b, c: ops.flash_attention_rope(
        a, b, c, pos, theta=1e6))(q, kv, kv).jaxpr
    eqns = list(_eqns(jaxpr))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert [e.params["name"] for e in calls] == ["flash_attention_rope_fwd"]
    in_kernel = {e.primitive.name for e in _eqns(calls[0].params["jaxpr"])}
    assert not {"sin", "cos"} & in_kernel
    assert {"sin", "cos"} <= {e.primitive.name for e in eqns}


# ---------------------------------------------------------------------------
# fused dkv + dq flash backward (one recompute feeds both)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("causal,window", [(True, None), (True, 13),
                                           (False, None)])
def test_flash_backward_fused_vs_split_vs_oracle(causal, window):
    """fuse_dq=True (single kernel, shared p blocks) == fuse_dq=False (two
    kernels, two recomputes) == the hand oracle VJP."""
    B, H, KV, T, hd = 2, 4, 2, 33, 32
    rng = jax.random.PRNGKey(29)
    q = jax.random.normal(rng, (B, H, T, hd))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, KV, T, hd))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (B, KV, T, hd))
    do = jax.random.normal(jax.random.fold_in(rng, 3), (B, H, T, hd))
    o, lse = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                    block_q=32, block_k=32,
                                    return_residuals=True, interpret=True)
    outs = {}
    for fuse in (True, False):
        outs[fuse] = flash_attention_backward_pallas(
            q, k, v, o, lse, do, causal=causal, window=window, block_q=32,
            block_k=32, fuse_dq=fuse, interpret=True)
    want = ref.attention_vjp_ref(q, k, v, do, causal=causal, window=window)
    for fuse in (True, False):
        for got, wnt in zip(outs[fuse], want):
            np.testing.assert_allclose(got, wnt, rtol=5e-4, atol=5e-4,
                                       err_msg=f"fuse_dq={fuse}")


def test_flash_backward_bf16_accumulators_bounded():
    """acc_dtype=bf16 on the fused path stays within bf16 resolution of the
    f32-accumulated grads (the docs/kernels.md accumulation study's bound)."""
    B, H, KV, T, hd = 2, 4, 2, 64, 32
    rng = jax.random.PRNGKey(31)
    q = jax.random.normal(rng, (B, H, T, hd))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, KV, T, hd))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (B, KV, T, hd))
    do = jax.random.normal(jax.random.fold_in(rng, 3), (B, H, T, hd))
    o, lse = flash_attention_pallas(q, k, v, causal=True, block_q=32,
                                    block_k=32, return_residuals=True,
                                    interpret=True)
    f32 = flash_attention_backward_pallas(
        q, k, v, o, lse, do, causal=True, block_q=32, block_k=32,
        fuse_dq=True, interpret=True)
    b16 = flash_attention_backward_pallas(
        q, k, v, o, lse, do, causal=True, block_q=32, block_k=32,
        fuse_dq=True, acc_dtype=jnp.bfloat16, interpret=True)
    for got, want, name in zip(b16, f32, ("dq", "dk", "dv")):
        scale = float(jnp.abs(want).max())
        err = float(jnp.abs(got.astype(jnp.float32) - want).max())
        # bf16 has ~8 mantissa bits; the accumulated sums lose a few more
        assert err <= 0.15 * scale, (name, err, scale)


# ---------------------------------------------------------------------------
# _fused_tile oracle fallback (never a silent mis-tile)
# ---------------------------------------------------------------------------


def test_fused_tile_gate():
    assert ops._fused_tile(256, "t") == 256
    assert ops._fused_tile(ops._MAX_FUSED_LANE, "t") == ops._MAX_FUSED_LANE
    with warnings_mod.catch_warnings():
        warnings_mod.simplefilter("ignore")
        assert ops._fused_tile(100, "t") is None
        assert ops._fused_tile(ops._MAX_FUSED_LANE + 128, "t") is None


def test_rmsnorm_residual_unaligned_fallback_warns_once():
    """d=100 (not a 128-multiple) falls back to the oracle — same numbers,
    ONE warning per shape, never a mis-tiled kernel."""
    N, d = 8, 100
    rng = jax.random.PRNGKey(37)
    x = jax.random.normal(rng, (N, d))
    r = jax.random.normal(jax.random.fold_in(rng, 1), (N, d))
    scale = jnp.linspace(0.5, 1.5, d)
    ops._TILE_WARNED.clear()
    with warnings_mod.catch_warnings(record=True) as rec:
        warnings_mod.simplefilter("always")
        y, s = ops.rmsnorm_residual(x, r, scale)
        yr, sr = ref.rmsnorm_residual_ref(x, r, scale)
        np.testing.assert_allclose(y, yr, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(s, sr, rtol=1e-6, atol=1e-6)
        gk = jax.grad(lambda *a: ops.rmsnorm_residual(*a)[0].sum(),
                      argnums=(0, 1, 2))(x, r, scale)
        gr = jax.grad(lambda *a: ref.rmsnorm_residual_ref(*a)[0].sum(),
                      argnums=(0, 1, 2))(x, r, scale)
        for g, w in zip(gk, gr):
            np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)
    hits = [w for w in rec if "rmsnorm_residual" in str(w.message)
            and "128-multiple" in str(w.message)]
    assert len(hits) == 1, [str(w.message) for w in rec]


def test_swiglu_unaligned_fallback_warns():
    """A non-128-multiple hidden dim falls back to the oracle (fwd + grad
    agree) with a warning."""
    N, d, F = 8, 128, 100
    rng = jax.random.PRNGKey(41)
    x = jax.random.normal(rng, (N, d))
    wg = jax.random.normal(jax.random.fold_in(rng, 1), (d, F)) / d ** 0.5
    wu = jax.random.normal(jax.random.fold_in(rng, 2), (d, F)) / d ** 0.5
    ops._TILE_WARNED.clear()
    with warnings_mod.catch_warnings(record=True) as rec:
        warnings_mod.simplefilter("always")
        h = ops.swiglu(x, wg, wu)
        hr, _ = ref.swiglu_ref(x, wg, wu)
        np.testing.assert_allclose(h, hr, rtol=1e-6, atol=1e-6)
        gk = jax.grad(lambda *a: ops.swiglu(*a).sum(),
                      argnums=(0, 1, 2))(x, wg, wu)
        gr = jax.grad(lambda *a: ref.swiglu_ref(*a)[0].sum(),
                      argnums=(0, 1, 2))(x, wg, wu)
        for g, w in zip(gk, gr):
            np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)
    assert any("swiglu" in str(w.message) and "128-multiple" in str(w.message)
               for w in rec)


def test_fallback_warning_is_an_error_when_promoted():
    """The fallback warnings carry their own category, so a run that must
    prove the kernels ran (chip_smoke.py) can promote exactly them to
    errors and leave every other warning alone."""
    x = jnp.ones((8, 128))
    wg = wu = jnp.ones((128, 100))
    ops._TILE_WARNED.clear()
    with warnings_mod.catch_warnings():
        warnings_mod.simplefilter("error", ops.KernelFallbackWarning)
        with pytest.raises(ops.KernelFallbackWarning, match="swiglu"):
            ops.swiglu(x, wg, wu)


# ---------------------------------------------------------------------------
# int8 paged KV cache
# ---------------------------------------------------------------------------


def _quantize_pool(kp):
    """Per-slot symmetric int8 quantization, as the engine/decode writes."""
    sc = jnp.maximum(jnp.abs(kp).max(axis=-1), 1e-8) / 127.0
    q = jnp.clip(jnp.round(kp / sc[..., None]), -127, 127).astype(jnp.int8)
    return q, sc.astype(jnp.float32)


def test_int8_roundtrip_error_bound():
    """quantize -> dequantize error is elementwise <= scale/2 (round), i.e.
    <= max|slot|/254; all-zero slots survive the clamped scale."""
    rng = jax.random.PRNGKey(43)
    kp = jax.random.normal(rng, (6, 2, 16, 64)) * \
        jnp.exp(jax.random.normal(jax.random.fold_in(rng, 1), (6, 1, 1, 1)))
    kp = kp.at[0].set(0.0)
    q, sc = _quantize_pool(kp)
    deq = q.astype(jnp.float32) * sc[..., None]
    err = jnp.abs(deq - kp)
    assert float((err - sc[..., None] / 2).max()) <= 1e-6
    np.testing.assert_array_equal(np.asarray(deq[0]), 0.0)
    # codes actually span the int8 range (the scale isn't degenerate)
    assert int(jnp.abs(q[1:]).max()) == 127


def _paged_from_contiguous(k, v, ps, seed=0):
    B, KV, S, hd = k.shape
    NB = S // ps
    perm = np.random.RandomState(seed).permutation(
        np.arange(1, 1 + B * NB)).astype(np.int32)
    pt = jnp.asarray(perm.reshape(B, NB))

    def pool(x):
        blocks = x.reshape(B, KV, NB, ps, hd).transpose(0, 2, 1, 3, 4)
        p = jnp.zeros((1 + B * NB, KV, ps, hd), x.dtype)
        return p.at[pt.reshape(-1)].set(blocks.reshape(B * NB, KV, ps, hd))
    return pool(k), pool(v), pt


def test_flash_decode_paged_int8_vs_oracle():
    """In-kernel dequant (pallas-interpret AND blockwise) == the oracle
    that materialises the dequantized pool up front, at per-row positions
    with window and fused-rope variants."""
    B, H, KV, NB, ps, hd = 2, 4, 2, 4, 16, 64
    S = NB * ps
    ks = jax.random.split(jax.random.PRNGKey(47), 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, KV, S, hd))
    v = jax.random.normal(ks[2], (B, KV, S, hd))
    pos = jnp.asarray([S - 1, S // 2 + 3], jnp.int32)
    kp, vp, pt = _paged_from_contiguous(k, v, ps)
    kq, ksc = _quantize_pool(kp)
    vq, vsc = _quantize_pool(vp)
    for window, theta in ((None, None), (24, None), (None, 1e4)):
        want = ref.flash_decode_paged_ref(q, kq, vq, pt, pos, window=window,
                                          k_scale=ksc, v_scale=vsc)
        if theta is not None:
            want = ref.flash_decode_paged_ref(
                ref.rope_ref(q[:, :, None], pos[:, None],
                             theta)[:, :, 0],
                kq, vq, pt, pos, window=window, k_scale=ksc, v_scale=vsc)
        for name, fn in (
            ("pallas", lambda *a, **kw: flash_decode_paged_pallas(
                *a, interpret=True, **kw)),
            ("blockwise", flash_decode_paged_blockwise),
        ):
            got = fn(q, kq, vq, pt, pos, window=window, k_scale=ksc,
                     v_scale=vsc, rope_theta=theta)
            np.testing.assert_allclose(got, want, atol=3e-6, rtol=1e-5,
                                       err_msg=f"{name} window={window} "
                                               f"theta={theta}")


def test_flash_decode_paged_int8_trash_page_noop():
    """Block-table entries past pos may point at trash page 0: with a
    quantized pool (page 0 codes AND scales are zeros) they must stay an
    exact no-op, and an all-trash row stays finite."""
    B, H, KV, NB, ps, hd = 2, 4, 2, 4, 16, 64
    S = NB * ps
    ks = jax.random.split(jax.random.PRNGKey(53), 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, KV, S, hd))
    v = jax.random.normal(ks[2], (B, KV, S, hd))
    pos = jnp.asarray([ps + 3, 2 * ps - 1], jnp.int32)   # rows use 2 blocks
    kp, vp, pt = _paged_from_contiguous(k, v, ps)
    kq, ksc = _quantize_pool(kp)
    vq, vsc = _quantize_pool(vp)
    full = flash_decode_paged_pallas(q, kq, vq, pt, pos, k_scale=ksc,
                                     v_scale=vsc, interpret=True)
    trashed = pt.at[:, 2:].set(0)
    for fn in (lambda *a, **kw: flash_decode_paged_pallas(
                   *a, interpret=True, **kw),
               flash_decode_paged_blockwise):
        got = fn(q, kq, vq, trashed, pos, k_scale=ksc, v_scale=vsc)
        np.testing.assert_allclose(got, full, atol=3e-6, rtol=1e-5)
        dead = fn(q, kq, vq, jnp.zeros_like(pt), pos, k_scale=ksc,
                  v_scale=vsc)
        assert np.isfinite(np.asarray(dead)).all()


# ---------------------------------------------------------------------------
# int8 cache through the model / engine
# ---------------------------------------------------------------------------


def _cfg(arch="qwen3-1.7b"):
    from repro.configs.registry import get_config
    return dataclasses.replace(get_config(arch).reduced(), dtype="float32")


@pytest.mark.parametrize("use_kernels", [False, True])
def test_decode_step_int8_bounded_logit_drift(use_kernels):
    """decode_step over an int8 paged cache tracks the full-precision paged
    cache within quantization noise (~1/254 relative on K/V) at every step
    — for both the kernel and the gather-dequant einsum paths."""
    from repro.models import transformer as T
    from repro.serving.engine import _write_pt
    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    B, S, ps = 2, 16, 8
    NB = S // ps
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, 10), 0,
                              cfg.vocab_size)
    out = {}
    for cd in (None, "int8"):
        cache = T.init_cache(cfg, B, S, dtype=jnp.float32, layout="paged",
                             page_size=ps, total_pages=1 + B * NB,
                             cache_dtype=cd)
        cache = _write_pt(cache, jnp.asarray(
            1 + np.arange(B * NB).reshape(B, NB), jnp.int32))
        seq = []
        for t in range(10):
            lg, cache = T.decode_step(params, cfg, toks[:, t:t + 1], cache,
                                      jnp.full((B,), t, jnp.int32),
                                      use_kernels=use_kernels)
            seq.append(lg[:, 0])
        out[cd] = jnp.stack(seq)
    drift = float(jnp.abs(out[None] - out["int8"]).max())
    scale = float(jnp.abs(out[None]).max())
    assert drift <= 0.06 * max(scale, 1.0), (drift, scale)
    assert drift > 0.0          # the quantized path actually ran


@pytest.mark.parametrize("use_kernels", [False, True])
def test_engine_int8_matches_full_precision_greedy(use_kernels):
    """ContinuousEngine(cache_dtype='int8') produces the SAME greedy tokens
    as the full-precision paged engine on the test trace (identical argmax
    per step), through admission quantization, slot reuse and retirement."""
    from repro.models import transformer as T
    from repro.serving import ContinuousEngine, Request
    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    r = np.random.RandomState(0)
    reqs = []
    for i in range(5):
        L = int(r.choice([4, 8]))
        prompt = r.randint(0, cfg.vocab_size, size=(L,)).astype("int32")
        reqs.append(Request(id=i, prompt=prompt, max_new_tokens=6,
                            arrival=0.9 * i))
    outs = {}
    for cd in (None, "int8"):
        eng = ContinuousEngine(params, cfg, num_slots=2, max_len=16,
                               layout="paged", page_size=8,
                               use_kernels=use_kernels, cache_dtype=cd)
        comps = eng.run(reqs)
        assert sorted(comps) == [q.id for q in reqs]
        outs[cd] = {i: c.tokens for i, c in comps.items()}
    assert outs[None] == outs["int8"]


def test_init_cache_int8_shapes():
    """The int8 paged cache carries int8 kp/vp plus f32 (pages, kv, ps)
    scale planes, and rejects non-paged layouts."""
    from repro.models import transformer as T
    cfg = _cfg()
    cache = T.init_cache(cfg, 2, 16, dtype=jnp.float32, layout="paged",
                         page_size=8, cache_dtype="int8")
    leaves = {}
    jax.tree_util.tree_map_with_path(
        lambda p, x: leaves.setdefault(
            "/".join(str(getattr(q, "key", "")) for q in p), x), cache)
    kp = next(v for k, v in leaves.items() if k.endswith("/kp"))
    ks = next(v for k, v in leaves.items() if k.endswith("/ks"))
    assert kp.dtype == jnp.int8
    assert ks.dtype == jnp.float32
    assert ks.shape == kp.shape[:-1]
    with pytest.raises(ValueError, match="cache_dtype"):
        T.init_cache(cfg, 2, 16, dtype=jnp.float32, layout="seq",
                     cache_dtype="int8")
