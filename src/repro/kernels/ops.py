"""Public jit'd wrappers for the Pallas kernels.

On TPU the kernels run compiled; everywhere else (this CPU container) they
run in ``interpret=True`` mode, which traces the kernel body to regular XLA
ops — bit-for-bit the same program structure, validated against the
pure-jnp oracles in :mod:`repro.kernels.ref`.

Every op here is differentiable through a dedicated Pallas backward kernel
wired up with ``jax.custom_vjp`` (see docs/kernels.md for each op's
forward/backward contract and residual layout) — ``jax.grad`` through the
``use_kernels=True`` training paths never falls back to
autodiff-through-interpret or to an oracle forward replay.

Each public op runs under a ``jax.named_scope`` of its kernel's name, and
every ``pallas_call`` under ``kernels/`` passes ``name=<kernel>_<fwd|bwd>
[_<part>]``, so a compiled instruction's ``op_name`` metadata says which
kernel it belongs to, forward or backward (docs/observability.md).
"""
from __future__ import annotations

import functools
import warnings
from typing import Optional, Set, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import (DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q,
                                           flash_attention_backward_pallas,
                                           flash_attention_pallas,
                                           flash_attention_rope_backward_pallas,
                                           flash_attention_rope_pallas)
from repro.kernels.flash_decode import (flash_decode_blockwise,
                                        flash_decode_paged_blockwise,
                                        flash_decode_paged_pallas,
                                        flash_decode_pallas)
from repro.kernels.fused_norm import (rmsnorm_residual_backward_pallas,
                                      rmsnorm_residual_pallas)
from repro.kernels.gbn import gbn_backward_pallas, gbn_forward_pallas
from repro.kernels.mamba_scan import (mamba_chunk_backward_pallas,
                                      mamba_chunk_pallas)
from repro.kernels.swiglu import swiglu_backward_pallas, swiglu_pallas


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     causal: bool, window: Optional[int],
                     block_q: int, block_k: int) -> jax.Array:
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  block_q=block_q, block_k=block_k,
                                  interpret=_interpret())


def _flash_fwd(q, k, v, causal, window, block_q, block_k):
    out, lse = flash_attention_pallas(
        q, k, v, causal=causal, window=window, block_q=block_q,
        block_k=block_k, return_residuals=True, interpret=_interpret())
    # residuals: the inputs, the output, and the per-row logsumexp — the
    # backward rebuilds the probability blocks from lse instead of saving
    # anything (T, S)-sized
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, block_q, block_k, res, do):
    q, k, v, out, lse = res
    return flash_attention_backward_pallas(
        q, k, v, out, lse, do, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=_interpret())


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


@jax.named_scope("flash_attention")
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    window: Optional[int] = None,
                    kv_offsets: Optional[jax.Array] = None) -> jax.Array:
    """Layout adapter for the model code: q (B, T, H, hd); k, v
    (B, S, KV, hd) -> (B, T, H, hd). Internally head-major.

    Differentiable: the backward is the dedicated Pallas kernel pair
    (:func:`repro.kernels.flash_attention.flash_attention_backward_pallas`)
    via ``jax.custom_vjp``, validated against
    :func:`repro.kernels.ref.attention_vjp_ref`.

    ``kv_offsets`` (B,) masks keys before each sequence's first real token
    (the serving fused prefill's left-padded ragged prompts). That path is
    FORWARD-ONLY — it bypasses the custom_vjp pair.
    """
    qm = q.swapaxes(1, 2)
    km = k.swapaxes(1, 2)
    vm = v.swapaxes(1, 2)
    if kv_offsets is not None:
        out = flash_attention_pallas(qm, km, vm, causal=causal,
                                     window=window, kv_offsets=kv_offsets,
                                     interpret=_interpret())
    else:
        out = _flash_attention(qm, km, vm, causal, window,
                               DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)
    return out.swapaxes(1, 2)


# ---------------------------------------------------------------------------
# flash decode (serving)
# ---------------------------------------------------------------------------


@jax.named_scope("flash_decode")
def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array, pos: jax.Array, *,
                 window: Optional[int] = None, ring: bool = False,
                 offsets: Optional[jax.Array] = None,
                 rope_theta: Optional[float] = None) -> jax.Array:
    """Single-row decode attention against a head-major cache.

    Layout adapter for the model code: q (B, 1, H, hd); k, v (B, KV, S, hd)
    -> (B, 1, H, hd). ``pos`` is a scalar or a per-row ``(B,)`` vector —
    both it and ``offsets`` are dynamic (per-row SMEM refs in the kernel);
    ``ring=True`` reads a sliding-window ring buffer of S slots.
    Forward-only (serving takes no gradients); oracle:
    :func:`repro.kernels.ref.flash_decode_ref`.

    On TPU the Pallas kernel runs compiled; elsewhere the SAME blockwise
    online-softmax program runs as a ``lax.scan``
    (:func:`repro.kernels.flash_decode.flash_decode_blockwise`) — unlike
    the training kernels, the decode hot loop cannot afford interpret-mode
    pallas emulation, whose per-grid-step cost scales with the full cache
    (the kernel body itself is oracle-validated under ``interpret=True`` in
    tests/test_serving.py).

    ``rope_theta`` fuses the query-row RoPE rotation (by ``pos - offset``)
    into the kernel — pass q UNROTATED; cached keys stay write-time rotated.
    """
    B, T, H, hd = q.shape
    assert T == 1, q.shape
    if _interpret():
        out = flash_decode_blockwise(q.reshape(B, H, hd), k, v, pos,
                                     window=window, ring=ring,
                                     offsets=offsets, rope_theta=rope_theta)
    else:
        out = flash_decode_pallas(q.reshape(B, H, hd), k, v, pos,
                                  window=window, ring=ring, offsets=offsets,
                                  rope_theta=rope_theta)
    return out.reshape(B, 1, H, hd)


@jax.named_scope("flash_decode_paged")
def flash_decode_paged(q: jax.Array, kp: jax.Array, vp: jax.Array,
                       pt: jax.Array, pos: jax.Array, *,
                       window: Optional[int] = None,
                       offsets: Optional[jax.Array] = None,
                       k_scale: Optional[jax.Array] = None,
                       v_scale: Optional[jax.Array] = None,
                       rope_theta: Optional[float] = None) -> jax.Array:
    """Paged-cache decode attention: q (B, 1, H, hd); kp, vp
    (n_pages, KV, page_size, hd) physical page pool; pt (B, n_blocks)
    int32 block tables -> (B, 1, H, hd).

    On TPU the Pallas kernel gathers pages via scalar-prefetch index maps;
    elsewhere the blockwise ``lax.scan`` gathers one page per row per step
    (:func:`repro.kernels.flash_decode.flash_decode_paged_blockwise`).
    Neither materialises a row's cache contiguously. Forward-only; oracle:
    :func:`repro.kernels.ref.flash_decode_paged_ref`.

    ``k_scale``/``v_scale`` (n_pages, KV, page_size) f32 mark an int8 pool
    (``cache_dtype="int8"``): pages dequantize at the load, inside the
    kernel. ``rope_theta`` fuses the query rotation as in
    :func:`flash_decode`.
    """
    B, T, H, hd = q.shape
    assert T == 1, q.shape
    if _interpret():
        out = flash_decode_paged_blockwise(q.reshape(B, H, hd), kp, vp, pt,
                                           pos, window=window,
                                           offsets=offsets, k_scale=k_scale,
                                           v_scale=v_scale,
                                           rope_theta=rope_theta)
    else:
        out = flash_decode_paged_pallas(q.reshape(B, H, hd), kp, vp, pt,
                                        pos, window=window, offsets=offsets,
                                        k_scale=k_scale, v_scale=v_scale,
                                        rope_theta=rope_theta)
    return out.reshape(B, 1, H, hd)


@jax.named_scope("flash_attention")
def flash_attention_hm(q: jax.Array, k: jax.Array, v: jax.Array, *,
                       causal: bool = True, window: Optional[int] = None,
                       block_q: int = DEFAULT_BLOCK_Q,
                       block_k: int = DEFAULT_BLOCK_K) -> jax.Array:
    """Head-major entry (B, H, T, hd) matching the oracle layout."""
    return _flash_attention(q, k, v, causal, window, block_q, block_k)


# ---------------------------------------------------------------------------
# ghost batch norm
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gbn_forward(xg: jax.Array, gamma: jax.Array, beta: jax.Array,
                 eps: float) -> Tuple[jax.Array, jax.Array, jax.Array]:
    return gbn_forward_pallas(xg, gamma, beta, eps=eps,
                              interpret=_interpret())


def _gbn_fwd(xg, gamma, beta, eps):
    y, mu, var = _gbn_forward(xg, gamma, beta, eps)
    # residuals are the input + the already-reduced stats — nothing
    # activation-sized is saved beyond x itself
    return (y, mu, var), (xg, gamma, beta, mu, var)


def _gbn_bwd(eps, res, cts):
    xg, gamma, beta, mu, var = res
    dy, dmu, dvar = cts
    dx, dgamma, dbeta = gbn_backward_pallas(
        xg, gamma, mu, var, dy, dmu, dvar, eps=eps, interpret=_interpret())
    return dx, dgamma.astype(gamma.dtype), dbeta.astype(beta.dtype)


_gbn_forward.defvjp(_gbn_fwd, _gbn_bwd)


@jax.named_scope("gbn")
def gbn_forward(xg: jax.Array, gamma: jax.Array, beta: jax.Array, *,
                eps: float = 1e-5) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """xg: (G, R, C) -> (y, mu (G,C), var (G,C)).

    Differentiable: the backward is the dedicated Pallas kernel
    (:func:`repro.kernels.gbn.gbn_backward_pallas`) via ``jax.custom_vjp``,
    validated against :func:`repro.kernels.ref.gbn_vjp_ref`.
    """
    return _gbn_forward(xg, gamma, beta, eps)


# ---------------------------------------------------------------------------
# mamba chunk scan
# ---------------------------------------------------------------------------

class KernelFallbackWarning(UserWarning):
    """A shape the kernel cannot tile legally: the op degrades to a larger
    untiled block or to the jnp oracle. A run that must prove the kernels
    ran (``chip_smoke.py``) turns this category into an error."""


# d_inner values we already warned about (one warning per distinct shape,
# not per trace): sub-lane-aligned fallback tiles and oracle fallbacks
_TILE_WARNED: Set[Tuple[int, str]] = set()


def _warn_once(di: int, kind: str, msg: str) -> None:
    if (di, kind) not in _TILE_WARNED:
        _TILE_WARNED.add((di, kind))
        warnings.warn(msg, KernelFallbackWarning, stacklevel=3)


# largest whole-axis (untiled) d_inner the kernel will take when no
# lane-aligned strict tile exists — bounds the VMEM block size
_MAX_UNTILED_DI = 1024


def _mamba_tile(di: int) -> Optional[int]:
    """Largest 128-multiple tile (<= 512) that divides d_inner, else the
    whole axis untiled.

    d_inner sits on the LANE axis of the x/dt blocks (and the sublane axis
    of the state blocks), so a strict sub-tile must be a 128-multiple to be
    legal off-interpret — when ``di % 128 != 0`` the only aligned option is
    the whole-axis block (Mosaic pads partial lanes of an untiled axis),
    which we take up to a VMEM bound. Returns None past that bound — the
    caller falls back to the jnp oracle. Both degraded paths warn once per
    shape so kernel-coverage regressions are visible instead of silent.
    """
    for cand in (512, 384, 256, 128):
        if di % cand == 0:
            return cand
    if di <= _MAX_UNTILED_DI:
        _warn_once(
            di, "untiled",
            f"mamba_chunk: d_inner={di} has no 128-multiple divisor; "
            f"running the whole axis as one untiled block (padded lanes, "
            f"larger VMEM working set)")
        return di
    _warn_once(
        di, "oracle",
        f"mamba_chunk: d_inner={di} has no 128-multiple divisor and is "
        f"too large for an untiled block; falling back to the un-tiled "
        f"jnp oracle (no kernel coverage)")
    return None


@jax.named_scope("mamba_scan")
def mamba_chunk(xc: jax.Array, dt: jax.Array, Bm: jax.Array, Cm: jax.Array,
                A: jax.Array, h0: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """Pallas chunk scan with a custom VJP: the forward runs the
    VMEM-resident kernel and the backward runs the dedicated reverse-time
    kernel (:func:`repro.kernels.mamba_scan.mamba_chunk_backward_pallas`) —
    no oracle forward replay; the chunk states are recomputed in VMEM
    scratch inside the backward kernel. Validated against
    :func:`repro.kernels.ref.mamba_chunk_vjp_ref`.
    """
    return _mamba_chunk(xc, dt, Bm, Cm, A, h0)


@jax.custom_vjp
def _mamba_chunk(xc, dt, Bm, Cm, A, h0):
    dit = _mamba_tile(xc.shape[-1])
    if dit is None:
        return ref.mamba_chunk_ref(xc, dt, Bm, Cm, A, h0)
    return mamba_chunk_pallas(xc, dt, Bm, Cm, A, h0, di_tile=dit,
                              interpret=_interpret())


def _mamba_chunk_fwd(xc, dt, Bm, Cm, A, h0):
    out = _mamba_chunk(xc, dt, Bm, Cm, A, h0)
    # residuals: the inputs only — the backward kernel recomputes the state
    # trajectory per chunk in VMEM, so nothing (B, c, di, ds)-sized is saved
    return out, (xc, dt, Bm, Cm, A, h0)


def _mamba_chunk_bwd(res, cts):
    xc, dt, Bm, Cm, A, h0 = res
    dit = _mamba_tile(xc.shape[-1])
    if dit is None:
        # the forward used the oracle; mirror it (shape-static decision)
        return ref.mamba_chunk_vjp_ref(xc, dt, Bm, Cm, A, h0, cts)
    dy, dh_last = cts
    return mamba_chunk_backward_pallas(xc, dt, Bm, Cm, A, h0, dy, dh_last,
                                       di_tile=dit, interpret=_interpret())


_mamba_chunk.defvjp(_mamba_chunk_fwd, _mamba_chunk_bwd)


# ---------------------------------------------------------------------------
# fused RoPE attention
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_attention_rope(q: jax.Array, k: jax.Array, v: jax.Array,
                          pos: jax.Array, theta: float, causal: bool,
                          window: Optional[int], block_q: int,
                          block_k: int) -> jax.Array:
    return flash_attention_rope_pallas(
        q, k, v, pos, theta=theta, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=_interpret())


def _flash_rope_fwd(q, k, v, pos, theta, causal, window, block_q, block_k):
    out, lse = flash_attention_rope_pallas(
        q, k, v, pos, theta=theta, causal=causal, window=window,
        block_q=block_q, block_k=block_k, return_residuals=True,
        interpret=_interpret())
    # residuals: the UNROTATED inputs (the backward re-rotates them — one
    # cheap elementwise pass), positions, output, and the logsumexp
    return out, (q, k, v, pos, out, lse)


def _flash_rope_bwd(theta, causal, window, block_q, block_k, res, do):
    q, k, v, pos, out, lse = res
    dq, dk, dv = flash_attention_rope_backward_pallas(
        q, k, v, pos, out, lse, do, theta=theta, causal=causal,
        window=window, block_q=block_q, block_k=block_k,
        interpret=_interpret())
    # positions are integral sampling points, not a continuous parameter
    return dq, dk, dv, jnp.zeros_like(pos)


_flash_attention_rope.defvjp(_flash_rope_fwd, _flash_rope_bwd)


@jax.named_scope("flash_attention_rope")
def flash_attention_rope(q: jax.Array, k: jax.Array, v: jax.Array,
                         positions: jax.Array, *, theta: float,
                         causal: bool = True,
                         window: Optional[int] = None) -> jax.Array:
    """Flash attention with RoPE fused into the q/k loads — the model-layout
    adapter: q (B, T, H, hd); k, v (B, T, KV, hd) UNROTATED; ``positions``
    broadcastable to (B, T) -> (B, T, H, hd). Replaces the separate
    ``apply_rope`` passes over q and k in the attention hot path.

    Differentiable via ``jax.custom_vjp``
    (:func:`repro.kernels.flash_attention.flash_attention_rope_backward_pallas`),
    validated against :func:`repro.kernels.ref.attention_rope_vjp_ref`.
    """
    B, T = q.shape[0], q.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(positions, jnp.float32), (B, T))
    out = _flash_attention_rope(q.swapaxes(1, 2), k.swapaxes(1, 2),
                                v.swapaxes(1, 2), pos, theta, causal,
                                window, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)
    return out.swapaxes(1, 2)


# ---------------------------------------------------------------------------
# fused row kernels (rmsnorm_residual, swiglu)
# ---------------------------------------------------------------------------

# widest whole-axis lane block the fused row kernels will take — their row
# blocks keep the full feature axis on the lane dimension
_MAX_FUSED_LANE = 8192


def _fused_tile(dim: int, kind: str) -> Optional[int]:
    """Feature-axis gate for the fused row kernels: the axis rides whole on
    the LANE dimension of each block, so it must be a 128-multiple and
    within a VMEM bound — otherwise the op falls back to the jnp oracle
    with a one-time warning (never a silent mis-tile)."""
    if dim % 128 == 0 and dim <= _MAX_FUSED_LANE:
        return dim
    if dim % 128:
        _warn_once(dim, kind,
                   f"{kind}: feature dim {dim} is not a 128-multiple; "
                   f"falling back to the jnp oracle (no kernel coverage)")
    else:
        _warn_once(dim, kind,
                   f"{kind}: feature dim {dim} exceeds the "
                   f"{_MAX_FUSED_LANE}-lane VMEM bound; falling back to the "
                   f"jnp oracle (no kernel coverage)")
    return None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rmsnorm_residual(x: jax.Array, r: jax.Array, scale: jax.Array,
                      eps: float) -> Tuple[jax.Array, jax.Array]:
    # off-TPU the fused jnp composition (XLA fuses the single pass) IS the
    # fast lowering — interpret-mode Pallas only re-runs it per grid step.
    # The kernel pair is the TPU path; tests drive it via interpret=True.
    d = x.shape[-1]
    if _fused_tile(d, "rmsnorm_residual") is None or _interpret():
        return ref.rmsnorm_residual_ref(x, r, scale, eps)
    shp = x.shape
    y, s = rmsnorm_residual_pallas(x.reshape(-1, d), r.reshape(-1, d),
                                   scale, eps=eps)
    return y.reshape(shp), s.reshape(shp)


def _rmsnorm_residual_fwd(x, r, scale, eps):
    y, s = _rmsnorm_residual(x, r, scale, eps)
    # residuals: the summed stream s (live anyway — it IS the second
    # output) and scale; x and r are never needed again
    return (y, s), (s, scale)


def _rmsnorm_residual_bwd(eps, res, cts):
    s, scale = res
    dy, ds = cts
    d = s.shape[-1]
    if _fused_tile(d, "rmsnorm_residual") is None or _interpret():
        # the forward used the oracle; its output depends on (x, r) only
        # through s = x + r, so re-linearize at (x=s, r=0)
        dx, _, dscale = ref.rmsnorm_residual_vjp_ref(
            s, jnp.zeros_like(s), scale, (dy, ds), eps)
        return dx, dx, dscale.astype(scale.dtype)
    dx, dscale = rmsnorm_residual_backward_pallas(
        s.reshape(-1, d), scale, dy.reshape(-1, d), ds.reshape(-1, d),
        eps=eps)
    dx = dx.reshape(s.shape)
    # the residual add fans the cotangent out equally: dr == dx
    return dx, dx, dscale.astype(scale.dtype)


_rmsnorm_residual.defvjp(_rmsnorm_residual_fwd, _rmsnorm_residual_bwd)


@jax.named_scope("rmsnorm_residual")
def rmsnorm_residual(x: jax.Array, r: jax.Array, scale: jax.Array, *,
                     eps: float = 1e-6) -> Tuple[jax.Array, jax.Array]:
    """Fused residual-add + RMSNorm: returns ``(rmsnorm(x + r) * scale,
    x + r)`` — the normed activations and the new residual stream — in one
    pass over (..., d). Differentiable via ``jax.custom_vjp``
    (:func:`repro.kernels.fused_norm.rmsnorm_residual_backward_pallas`),
    validated against :func:`repro.kernels.ref.rmsnorm_residual_vjp_ref`.
    Non-128-multiple ``d`` falls back to the oracle (one-time warning).
    """
    return _rmsnorm_residual(x, r, scale, eps)


@jax.named_scope("swiglu")
def swiglu(x: jax.Array, wg: jax.Array, wu: jax.Array) -> jax.Array:
    """Fused SwiGLU front half: ``silu(x @ wg) * (x @ wu)`` over (..., d)
    with one pass over x and a single saved hidden activation (the gate
    pre-activation; the up projection is recomputed by the backward).
    Differentiable via ``jax.custom_vjp``
    (:func:`repro.kernels.swiglu.swiglu_backward_pallas`), validated
    against :func:`repro.kernels.ref.swiglu_vjp_ref`. Non-128-multiple
    ``d``/hidden dims fall back to the oracle (one-time warning).
    """
    return _swiglu(x, wg, wu)


@jax.custom_vjp
def _swiglu(x, wg, wu):
    h, _ = _swiglu_impl(x, wg, wu)
    return h


def _swiglu_impl(x, wg, wu):
    # same off-TPU discipline as _rmsnorm_residual: jnp lowering off-TPU
    # (the tile gate still runs first so misaligned dims warn everywhere),
    # Pallas pair on TPU.
    d, F = wg.shape
    aligned = (_fused_tile(d, "swiglu") is not None
               and _fused_tile(F, "swiglu") is not None)
    if not aligned or _interpret():
        # single concatenated GEMM (one pass over x, gate in the epilogue);
        # XLA CPU lowers the naive two-GEMM composition measurably slower.
        dt = x.dtype
        gu = x @ jnp.concatenate([wg, wu], axis=1).astype(dt)
        g, u = jnp.split(gu, 2, axis=-1)
        return (jax.nn.silu(g) * u).astype(dt), None  # no gate residual
    shp = x.shape
    h, g = swiglu_pallas(x.reshape(-1, d), wg, wu)
    return h.reshape(shp[:-1] + (F,)), g


def _swiglu_fwd(x, wg, wu):
    h, g = _swiglu_impl(x, wg, wu)
    # residuals: inputs + the (N, F) gate pre-activation (None on the
    # oracle path — shape-static decision mirrored in the backward)
    return h, (x, wg, wu, g)


def _swiglu_bwd(res, dh):
    x, wg, wu, g = res
    if g is None:
        return ref.swiglu_vjp_ref(x, wg, wu, dh)
    d, F = wg.shape
    x2 = x.reshape(-1, d)
    dx, dg, du = swiglu_backward_pallas(x2, wg, wu, g, dh.reshape(-1, F))
    # weight grads are plain GEMMs over the full dg/du — nothing to fuse
    dwg = jnp.dot(x2.T.astype(jnp.float32),
                  dg.astype(jnp.float32)).astype(wg.dtype)
    dwu = jnp.dot(x2.T.astype(jnp.float32),
                  du.astype(jnp.float32)).astype(wu.dtype)
    return dx.astype(x.dtype).reshape(x.shape), dwg, dwu


_swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)
