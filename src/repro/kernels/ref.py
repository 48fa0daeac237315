"""Pure-jnp oracles for every Pallas kernel in this package.

Each kernel's tests sweep shapes/dtypes and assert_allclose against these.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# flash attention oracle
# ---------------------------------------------------------------------------


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True,
                  window: Optional[int] = None) -> jax.Array:
    """q: (B, H, T, hd); k, v: (B, KV, S, hd). Returns (B, H, T, hd).

    GQA: head h uses kv head h // (H // KV).
    """
    B, H, T, hd = q.shape
    KV, S = k.shape[1], k.shape[2]
    g = H // KV
    qg = q.reshape(B, KV, g, T, hd)
    logits = jnp.einsum("bkgtd,bksd->bkgts", qg,
                        k).astype(jnp.float32) / math.sqrt(hd)
    qi = jnp.arange(T)[:, None]
    ki = jnp.arange(S)[None, :]
    mask = jnp.ones((T, S), dtype=bool)
    if causal:
        mask &= ki <= qi
    if window is not None:
        mask &= ki > qi - window
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgts,bksd->bkgtd", p, v)
    return out.reshape(B, H, T, hd)


def attention_vjp_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                      do: jax.Array, *, causal: bool = True,
                      window: Optional[int] = None
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Hand-derived pure-jnp VJP of :func:`attention_ref` w.r.t. (q, k, v).

    q, do: (B, H, T, hd); k, v: (B, KV, S, hd). Returns (dq, dk, dv) in the
    input dtypes (dk/dv summed over each GQA q-head group).

    Standard softmax-attention backward (f32 throughout): with
    ``p = softmax(q k^T / sqrt(hd))`` and ``delta = rowsum(do * o)``,

        dv = p^T do
        ds = p * (do v^T - delta) / sqrt(hd)
        dq = ds k,   dk = ds^T q
    """
    B, H, T, hd = q.shape
    KV, S = k.shape[1], k.shape[2]
    g = H // KV
    scale = 1.0 / math.sqrt(hd)
    qg = q.astype(jnp.float32).reshape(B, KV, g, T, hd)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    dog = do.astype(jnp.float32).reshape(B, KV, g, T, hd)

    logits = jnp.einsum("bkgtd,bksd->bkgts", qg, kf) * scale
    qi = jnp.arange(T)[:, None]
    ki = jnp.arange(S)[None, :]
    mask = jnp.ones((T, S), dtype=bool)
    if causal:
        mask &= ki <= qi
    if window is not None:
        mask &= ki > qi - window
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)                  # (B, KV, g, T, S)

    dv = jnp.einsum("bkgts,bkgtd->bksd", p, dog)
    dp = jnp.einsum("bkgtd,bksd->bkgts", dog, vf)
    delta = jnp.sum(p * dp, axis=-1, keepdims=True)      # rowsum(do * o)
    ds = p * (dp - delta) * scale
    dq = jnp.einsum("bkgts,bksd->bkgtd", ds, kf).reshape(B, H, T, hd)
    dk = jnp.einsum("bkgts,bkgtd->bksd", ds, qg)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# flash decode oracle
# ---------------------------------------------------------------------------


def flash_decode_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                     pos: jax.Array, *, window: Optional[int] = None,
                     ring: bool = False,
                     offsets: Optional[jax.Array] = None) -> jax.Array:
    """Single-row decode attention vs a cache. q: (B, H, hd); k, v:
    (B, KV, S, hd). Returns (B, H, hd).

    ``pos`` is a scalar or a per-row ``(B,)`` vector of query positions.
    Slot ``s`` holds global position ``s`` (``ring=False``) or
    ``pos - ((pos - s) mod S)`` (ring buffer of S slots). A slot with global
    position g is visible iff ``0 <= g <= pos``, ``g > pos - window`` (when
    windowed) and ``g >= offsets[b]`` (left-padded ragged prompts).
    """
    B, H, hd = q.shape
    KV, S = k.shape[1], k.shape[2]
    g = H // KV
    qg = q.astype(jnp.float32).reshape(B, KV, g, hd)
    logits = jnp.einsum("bkgd,bksd->bkgs", qg,
                        k.astype(jnp.float32)) / math.sqrt(hd)
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1),
                            (B,))[:, None]                     # (B, 1)
    slot = jnp.arange(S)[None, :]                              # (1, S)
    gpos = posb - jnp.mod(posb - slot, S) if ring \
        else jnp.broadcast_to(slot, (B, S))
    valid = (gpos >= 0) & (gpos <= posb)                       # (B, S)
    if window is not None:
        valid &= gpos > posb - window
    if offsets is not None:
        valid &= gpos >= offsets[:, None]
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bksd->bkgd", p, v.astype(jnp.float32))
    return out.reshape(B, H, hd).astype(q.dtype)


def flash_decode_paged_ref(q: jax.Array, kp: jax.Array, vp: jax.Array,
                           pt: jax.Array, pos: jax.Array, *,
                           window: Optional[int] = None,
                           offsets: Optional[jax.Array] = None,
                           k_scale: Optional[jax.Array] = None,
                           v_scale: Optional[jax.Array] = None) -> jax.Array:
    """Paged-cache decode oracle: gather each row's pages into a contiguous
    (B, KV, n_blocks*page_size, hd) cache and defer to
    :func:`flash_decode_ref` — the thing the paged kernel exists to avoid
    doing, which is exactly what makes it the oracle. kp, vp:
    (n_pages, KV, page_size, hd); pt: (B, n_blocks).

    ``k_scale``/``v_scale`` (n_pages, KV, page_size) dequantize an int8
    pool: the stored value is ``round(k / scale)`` and the oracle
    materialises ``kp * scale`` up front — the full-precision gather the
    in-kernel dequant exists to avoid."""
    B = q.shape[0]
    KV, ps, hd = kp.shape[1], kp.shape[2], kp.shape[3]
    NB = pt.shape[1]
    if k_scale is not None:
        kp = kp.astype(jnp.float32) * k_scale[..., None]
        vp = vp.astype(jnp.float32) * v_scale[..., None]
        kp = kp.astype(q.dtype)
        vp = vp.astype(q.dtype)
    k = kp[pt].transpose(0, 2, 1, 3, 4).reshape(B, KV, NB * ps, hd)
    v = vp[pt].transpose(0, 2, 1, 3, 4).reshape(B, KV, NB * ps, hd)
    return flash_decode_ref(q, k, v, pos, window=window, ring=False,
                            offsets=offsets)


# ---------------------------------------------------------------------------
# rotary embedding / fused-RoPE attention oracle
# ---------------------------------------------------------------------------


def rope_ref(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Head-major half-rotation RoPE: x (B, H, T, hd), pos (B, T).

    Mirrors ``models.layers.apply_rope`` (llama convention:
    ``freqs_i = theta ** -(i / (hd/2))``) on the kernel layout; the RoPE
    attention entry and the decode kernels are checked against it."""
    dt = x.dtype
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None, :, None] * freqs  # (B,1,T,half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(dt)


def attention_rope_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                       pos: jax.Array, *, theta: float,
                       causal: bool = True,
                       window: Optional[int] = None) -> jax.Array:
    """Oracle for the RoPE-fused flash attention: the unfused composition
    ``attention_ref(rope(q), rope(k), v)`` the kernel folds into one pass.
    q: (B, H, T, hd); k, v: (B, KV, T, hd); pos: (B, T) shared q/k
    positions (self-attention)."""
    return attention_ref(rope_ref(q, pos, theta), rope_ref(k, pos, theta),
                         v, causal=causal, window=window)


def attention_rope_vjp_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                           pos: jax.Array, do: jax.Array, *, theta: float,
                           causal: bool = True,
                           window: Optional[int] = None
                           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Oracle VJP of :func:`attention_rope_ref` w.r.t. (q, k, v):
    autodiff of the unfused jnp composition."""
    def f(q_, k_, v_):
        return attention_rope_ref(q_, k_, v_, pos, theta=theta,
                                  causal=causal, window=window)
    _, vjp = jax.vjp(f, q, k, v)
    return vjp(do)


# ---------------------------------------------------------------------------
# fused rmsnorm + residual oracle
# ---------------------------------------------------------------------------


def rmsnorm_residual_ref(x: jax.Array, r: jax.Array, scale: jax.Array,
                         eps: float = 1e-6
                         ) -> Tuple[jax.Array, jax.Array]:
    """Fused residual-add + RMSNorm oracle: ``s = x + r`` (the new residual
    stream) and ``y = rmsnorm(s) * scale``, both in one pass.

    x, r: (..., d); scale: (d,). Mirrors ``models.layers.rmsnorm_apply``
    (f32 compute, cast back to the input dtype). Returns (y, s)."""
    dt = x.dtype
    s = x + r
    sf = s.astype(jnp.float32)
    var = jnp.mean(jnp.square(sf), axis=-1, keepdims=True)
    y = sf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return y.astype(dt), s


def rmsnorm_residual_vjp_ref(x: jax.Array, r: jax.Array, scale: jax.Array,
                             cts: Tuple[jax.Array, jax.Array],
                             eps: float = 1e-6) -> Tuple[jax.Array, ...]:
    """Oracle VJP of :func:`rmsnorm_residual_ref` w.r.t. (x, r, scale):
    autodiff of the jnp oracle. ``cts = (dy, ds)`` — both forward outputs
    are live (``s`` feeds the next residual add)."""
    _, vjp = jax.vjp(lambda a, b, c: rmsnorm_residual_ref(a, b, c, eps),
                     x, r, scale)
    return vjp(cts)


# ---------------------------------------------------------------------------
# fused SwiGLU oracle
# ---------------------------------------------------------------------------


def swiglu_ref(x: jax.Array, wg: jax.Array, wu: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
    """Fused SwiGLU oracle: ``h = silu(x @ wg) * (x @ wu)`` plus the single
    hidden-activation residual ``g = x @ wg`` the backward keeps (``u`` is
    recomputed). x: (..., d); wg, wu: (d, f). Returns (h, g)."""
    dt = x.dtype
    g = x @ wg.astype(dt)
    u = x @ wu.astype(dt)
    return jax.nn.silu(g) * u, g


def swiglu_vjp_ref(x: jax.Array, wg: jax.Array, wu: jax.Array,
                   dh: jax.Array) -> Tuple[jax.Array, ...]:
    """Oracle VJP of the SwiGLU output ``h`` w.r.t. (x, wg, wu): autodiff
    of the jnp composition (``g`` is an internal residual, not a
    user-visible output — its cotangent is zero)."""
    _, vjp = jax.vjp(lambda a, b, c: swiglu_ref(a, b, c)[0], x, wg, wu)
    return vjp(dh)


# ---------------------------------------------------------------------------
# ghost batch norm oracle
# ---------------------------------------------------------------------------


def gbn_ref(xg: jax.Array, gamma: jax.Array, beta: jax.Array, *,
            eps: float = 1e-5) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """xg: (G, R, C) -> (y (G,R,C), mu (G,C), var (G,C)); biased variance."""
    xf = xg.astype(jnp.float32)
    mu = xf.mean(axis=1)
    var = jnp.mean(jnp.square(xf - mu[:, None, :]), axis=1)
    y = (xf - mu[:, None, :]) * jax.lax.rsqrt(var[:, None, :] + eps)
    y = y * gamma.astype(jnp.float32) + beta.astype(jnp.float32)
    return y.astype(xg.dtype), mu, var


def gbn_vjp_ref(xg: jax.Array, gamma: jax.Array, beta: jax.Array,
                cts: Tuple[jax.Array, jax.Array, jax.Array], *,
                eps: float = 1e-5
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Hand-derived pure-jnp VJP of :func:`gbn_ref`.

    ``cts = (dy, dmu, dvar)`` are the cotangents of the three forward
    outputs (the mu/var cotangents are live: the leftover-rows path in
    ``core.gbn`` normalizes its tail with the last ghost's statistics, so
    the loss really does depend on them). Returns (dx, dgamma, dbeta).

    Standard BN backward, per ghost, with the upstream stat cotangents
    folded in (``gvar``/``gmu`` are the TOTAL adjoints of var/mu):

        gvar = dvar - 1/2 gamma rstd^2 sum_r dy xhat
        gmu  = dmu  - gamma rstd sum_r dy
        dx_r = gamma rstd dy_r + 2 gvar (x_r - mu)/R + gmu/R
    """
    dy, dmu, dvar = cts
    xf = xg.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    g = gamma.astype(jnp.float32)
    R = xg.shape[1]

    mu = xf.mean(axis=1)                                         # (G, C)
    var = jnp.mean(jnp.square(xf - mu[:, None, :]), axis=1)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (xf - mu[:, None, :]) * rstd[:, None, :]

    sdy = dyf.sum(axis=1)                                        # (G, C)
    sdyxh = jnp.sum(dyf * xhat, axis=1)
    gvar = dvar.astype(jnp.float32) - 0.5 * g * rstd * rstd * sdyxh
    gmu = dmu.astype(jnp.float32) - g * rstd * sdy

    dx = dyf * (g * rstd)[:, None, :] \
        + (xf - mu[:, None, :]) * (2.0 * gvar / R)[:, None, :] \
        + (gmu / R)[:, None, :]
    dgamma = sdyxh.sum(axis=0)
    dbeta = sdy.sum(axis=0)
    return (dx.astype(xg.dtype), dgamma.astype(gamma.dtype),
            dbeta.astype(beta.dtype))


# ---------------------------------------------------------------------------
# mamba chunk-scan oracle
# ---------------------------------------------------------------------------


def mamba_chunk_ref(xc: jax.Array, dt: jax.Array, Bm: jax.Array,
                    Cm: jax.Array, A: jax.Array, h0: jax.Array
                    ) -> Tuple[jax.Array, jax.Array]:
    """Sequential reference for one chunk of the selective scan.

    xc, dt: (B, c, di); Bm, Cm: (B, c, ds); A: (di, ds); h0: (B, di, ds).
    Returns (y (B, c, di) f32, h_last (B, di, ds) f32).
    """
    xc = xc.astype(jnp.float32)
    dt = dt.astype(jnp.float32)
    Bm = Bm.astype(jnp.float32)
    Cm = Cm.astype(jnp.float32)

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        a = jnp.exp(dt_t[:, :, None] * A)            # (B, di, ds)
        h = a * h + (dt_t * x_t)[:, :, None] * b_t[:, None, :]
        y = jnp.einsum("bds,bs->bd", h, c_t)
        return h, y

    inps = (xc.swapaxes(0, 1), dt.swapaxes(0, 1),
            Bm.swapaxes(0, 1), Cm.swapaxes(0, 1))
    h_last, ys = jax.lax.scan(step, h0.astype(jnp.float32), inps)
    return ys.swapaxes(0, 1), h_last


def mamba_chunk_vjp_ref(xc: jax.Array, dt: jax.Array, Bm: jax.Array,
                        Cm: jax.Array, A: jax.Array, h0: jax.Array,
                        cts: Tuple[jax.Array, jax.Array]
                        ) -> Tuple[jax.Array, ...]:
    """Oracle VJP of :func:`mamba_chunk_ref` w.r.t. all six inputs.

    ``cts = (dy, dh_last)`` are the cotangents of the two forward outputs.
    Returns (dxc, ddt, dB, dC, dA, dh0). Autodiff of the jnp oracle — the
    dedicated backward kernel is validated against this.
    """
    _, vjp = jax.vjp(mamba_chunk_ref, xc, dt, Bm, Cm, A, h0)
    return vjp(cts)
