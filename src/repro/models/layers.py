"""Core neural-net layers: norms, RoPE, GQA attention (full / sliding-window /
cross), SwiGLU MLP.

All layers are pure functions over explicit parameter pytrees:

    params = <layer>_init(rng, ...)
    y      = <layer>_apply(params, x, ...)

Compute happens in ``compute_dtype`` (bf16 on the production configs, fp32 in
smoke tests); parameters are stored in the dtype they were initialised with.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.sharding.hints import hint, model_axis_if

Params = Dict[str, Any]

# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------


def dense_init(rng, shape, scale: Optional[float] = None, dtype=jnp.float32):
    """Glorot/He-style scaled normal init (paper uses Glorot & Bengio 2010)."""
    fan_in = shape[0] if len(shape) >= 1 else 1
    if scale is None:
        scale = 1.0 / math.sqrt(max(fan_in, 1))
    return (scale * jax.random.normal(rng, shape, dtype=jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rmsnorm_init(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype=dtype)}


def rmsnorm_apply(params: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dt)


def layernorm_init(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype=dtype),
            "bias": jnp.zeros((d,), dtype=dtype)}


def layernorm_apply(params: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)
            + params["bias"].astype(jnp.float32)).astype(dt)


def norm_init(cfg: ModelConfig, d: int, dtype=jnp.float32) -> Params:
    if cfg.norm.kind == "layernorm":
        return layernorm_init(d, dtype)
    return rmsnorm_init(d, dtype)


@jax.named_scope("norm")
def norm_apply(cfg: ModelConfig, params: Params, x: jax.Array) -> jax.Array:
    """The pre-sublayer (and final) norm; qk-norm calls
    :func:`rmsnorm_apply` itself, inside its attention scope."""
    if cfg.norm.kind == "layernorm":
        return layernorm_apply(params, x, cfg.norm.eps)
    return rmsnorm_apply(params, x, cfg.norm.eps)


@jax.named_scope("norm")
def norm_residual_apply(cfg: ModelConfig, params: Params, x: jax.Array,
                        r: jax.Array, *, use_kernels: bool = False
                        ) -> Tuple[jax.Array, jax.Array]:
    """Fused sublayer seam: residual add + pre-norm in one pass. Returns
    ``(norm(x + r) * scale, x + r)`` — the normed input of the next sublayer
    and the new residual stream. The fused Pallas kernel
    (:func:`repro.kernels.ops.rmsnorm_residual`) only covers rmsnorm; the
    layernorm configs take the unfused two-pass path."""
    if use_kernels and cfg.norm.kind == "rmsnorm":
        from repro.kernels import ops as kops
        return kops.rmsnorm_residual(x, r, params["scale"], eps=cfg.norm.eps)
    s = x + r
    return norm_apply(cfg, params, s), s


# ---------------------------------------------------------------------------
# rotary position embedding (half-rotation / llama convention)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., T, H, hd); positions: broadcastable to (..., T)."""
    dt = x.dtype
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                          # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (..., T, hd/2)
    angles = angles[..., None, :]                          # (..., T, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(dt)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def attention_init(rng, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(ks[0], (d, h * hd), dtype=dtype),
        "wk": dense_init(ks[1], (d, kv * hd), dtype=dtype),
        "wv": dense_init(ks[2], (d, kv * hd), dtype=dtype),
        "wo": dense_init(ks[3], (h * hd, d), dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, dtype)
        p["k_norm"] = rmsnorm_init(hd, dtype)
    return p


def _project_qkv(params: Params, cfg: ModelConfig, x: jax.Array,
                 positions: Optional[jax.Array], rope: bool = True):
    B = x.shape[0]
    T = x.shape[1]
    hd = cfg.head_dim
    dt = x.dtype
    # head counts come from the WEIGHT shapes, not cfg: under Megatron-style
    # tensor parallelism the shard_map region hands this function the local
    # head-slice (h/msize heads), and every downstream op is per-head.
    h = params["wq"].shape[-1] // hd
    kv = params["wk"].shape[-1] // hd
    q = (x @ params["wq"].astype(dt)).reshape(B, T, h, hd)
    k = (x @ params["wk"].astype(dt)).reshape(B, T, kv, hd)
    v = (x @ params["wv"].astype(dt)).reshape(B, T, kv, hd)
    if cfg.qk_norm:
        q = rmsnorm_apply(params["q_norm"], q, cfg.norm.eps)
        k = rmsnorm_apply(params["k_norm"], k, cfg.norm.eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(q: jax.Array, k: jax.Array, v: jax.Array,
          mask: Optional[jax.Array]) -> jax.Array:
    """q: (B,T,h,hd); k,v: (B,S,kv,hd). GQA: kv heads are repeated to h —
    the repeat is transient (layer-local) and lets the head axis shard over
    the 'model' mesh axis regardless of the kv:q ratio.
    mask: broadcastable to (B, T, S), True = attend."""
    B, T, h, hd = q.shape
    S, kv = k.shape[1], k.shape[2]
    g = h // kv
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    q = hint(q, "dp", None, "model", None)
    k = hint(k, "dp", None, "model", None)
    v = hint(v, "dp", None, "model", None)
    logits = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32)
    logits = hint(logits / math.sqrt(hd), "dp", "model", None, None)
    if mask is not None:
        m = jnp.broadcast_to(mask, (B,) + mask.shape[-2:])
        logits = jnp.where(m[:, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhts,bshd->bthd", probs, v)
    return hint(out, "dp", None, "model", None)


def _sdpa_grouped(q: jax.Array, k: jax.Array, v: jax.Array,
                  mask: Optional[jax.Array]) -> jax.Array:
    """Decode-path attention WITHOUT repeating K/V to full heads (§Perf:
    repeating a 500k-token cache materialises gigabytes per layer per token).
    q: (B,T,h,hd); k,v: (B,S,kv,hd); GQA via grouped einsum; kv heads are
    sharded over 'model' when divisible (cache rule), so hint accordingly."""
    B, T, h, hd = q.shape
    S, kv = k.shape[1], k.shape[2]
    g = h // kv
    kv_ax = model_axis_if(kv)   # shard kv heads only when they divide evenly
    qg = q.reshape(B, T, kv, g, hd)
    if kv_ax is not None:
        # kv-head-parallel decode: keep q/k/v and logits head-sharded
        qg = hint(qg, "dp", None, kv_ax, None, None)
        k = hint(k, "dp", None, kv_ax, None)
        v = hint(v, "dp", None, kv_ax, None)
    # else: leave k/v alone — the cache is sequence-sharded over 'model'
    # (rules.cache_specs) and forcing replication here would all-gather it.
    logits = jnp.einsum("btkgd,bskd->bktgs", qg, k).astype(jnp.float32)
    logits = logits / math.sqrt(hd)
    if kv_ax is not None:
        logits = hint(logits, "dp", kv_ax, None, None, None)
    if mask is not None:
        m = jnp.broadcast_to(mask, (B,) + mask.shape[-2:])
        logits = jnp.where(m[:, None, :, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bktgs,bskd->btkgd", probs, v)
    return hint(out.reshape(B, T, h, hd), "dp", None, None, None)


def causal_mask(T: int, S: int, offset: int = 0) -> jax.Array:
    """True where query t (global index t+offset) may attend key s."""
    qi = jnp.arange(T)[:, None] + offset
    ki = jnp.arange(S)[None, :]
    return ki <= qi


def window_mask(T: int, S: int, window: int, offset: int = 0) -> jax.Array:
    qi = jnp.arange(T)[:, None] + offset
    ki = jnp.arange(S)[None, :]
    return (ki <= qi) & (ki > qi - window)


def _local_attention(q, k, v, window: int, dtype) -> jax.Array:
    """Block-local sliding-window attention with O(T * 2*window) cost.

    Pads T to a multiple of ``window``; each query block attends its own and
    the previous key block, masked to exactly ``window`` history.
    """
    B, T, h, hd = q.shape
    kv = k.shape[2]
    W = window
    Tp = (T + W - 1) // W * W
    pad = Tp - T
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nb = Tp // W
    g = h // kv
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    qb = hint(q.reshape(B, nb, W, h, hd), "dp", None, None, "model", None)
    kb = hint(k.reshape(B, nb, W, h, hd), "dp", None, None, "model", None)
    vb = hint(v.reshape(B, nb, W, h, hd), "dp", None, None, "model", None)
    # keys for block i = concat(block i-1, block i): (B, nb, 2W, h, hd)
    kprev = jnp.concatenate([jnp.zeros_like(kb[:, :1]), kb[:, :-1]], axis=1)
    vprev = jnp.concatenate([jnp.zeros_like(vb[:, :1]), vb[:, :-1]], axis=1)
    k2 = jnp.concatenate([kprev, kb], axis=2)
    v2 = jnp.concatenate([vprev, vb], axis=2)
    logits = jnp.einsum("bnwhd,bnshd->bnhws", qb, k2).astype(jnp.float32)
    logits = hint(logits / math.sqrt(hd), "dp", None, "model", None, None)
    # in-block relative positions: query w (0..W-1) at global offset W + w
    qi = jnp.arange(W)[:, None] + W
    ki = jnp.arange(2 * W)[None, :]
    m = (ki <= qi) & (ki > qi - W)                  # (W, 2W)
    # first block has no previous block
    first = jnp.arange(nb)[:, None, None] > 0
    m = m[None] & (first | (ki[None] >= W))
    logits = jnp.where(m[None, :, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(dtype)
    out = jnp.einsum("bnhws,bnshd->bnwhd", probs, v2)
    out = out.reshape(B, Tp, h, hd)
    return out[:, :T]


def attention_full(params: Params, cfg: ModelConfig, x: jax.Array,
                   positions: jax.Array, *, window: Optional[int] = None,
                   causal: bool = True,
                   segment_mask: Optional[jax.Array] = None,
                   use_kernels: bool = False) -> jax.Array:
    """Self-attention over a full sequence (training / prefill)."""
    B, T, _ = x.shape
    if use_kernels and causal and segment_mask is None:
        from repro.kernels import ops as kops
        # the kernel entry rotates q/k itself, in f32, with its head-major
        # copies (no bf16 apply_rope pass here)
        q, k, v = _project_qkv(params, cfg, x, positions, rope=False)
        out = kops.flash_attention_rope(q, k, v, positions,
                                        theta=cfg.rope_theta, causal=True,
                                        window=window)
        return out.reshape(B, T, -1) @ params["wo"].astype(x.dtype)
    q, k, v = _project_qkv(params, cfg, x, positions)
    if window is not None and causal and T > 2 * window and segment_mask is None:
        out = _local_attention(q, k, v, window, x.dtype)
    else:
        if causal:
            m = (window_mask(T, T, window) if window is not None
                 else causal_mask(T, T))
        else:
            m = jnp.ones((T, T), dtype=bool)
        if segment_mask is not None:
            m = m & segment_mask
        out = _sdpa(q, k, v, m[None] if m.ndim == 2 else m)
    return out.reshape(B, T, -1) @ params["wo"].astype(x.dtype)


# -- decode (one new token against a KV cache) ------------------------------


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  window: Optional[int] = None, dtype=jnp.bfloat16,
                  layout: str = "seq", page_size: int = 64,
                  total_pages: Optional[int] = None,
                  cache_dtype: Optional[str] = None) -> Params:
    """KV cache for one attention layer. SWA layers use a ring buffer of
    ``window`` slots; full layers allocate ``max_len``.

    ``layout="seq"`` stores (B, S, kv, hd) — the layout the grouped-einsum
    decode path and the sharding rules expect. ``layout="head"`` stores
    (B, kv, S, hd) under keys ``kh``/``vh`` — the flash-decode kernel's
    native layout (the sequence axis lands on the sublane axis of its KV
    blocks). ``layout="paged"`` stores a physical page pool ``kp``/``vp``
    (total_pages, kv, page_size, hd) plus per-row int32 block tables ``pt``
    (batch, ceil(max_len / page_size)) mapping logical block i to a
    physical page — the continuous-batching layout where rows reserve
    pages as they grow instead of worst-case contiguous memory. Physical
    page 0 is RESERVED as the trash page: unallocated / retired table
    entries point at it, so stray writes land somewhere harmless and the
    kernel's gather never reads out of bounds. SWA layers under "paged"
    fall back to the head-major ring (a window-bounded ring is already its
    own worst case — paging it buys nothing). The key names carry the
    layout, so every consumer can self-describe instead of threading a
    flag.

    ``cache_dtype="int8"`` (paged only; other layouts raise) stores the
    page pool as int8 codes with per-slot f32 scales ``ks``/``vs``
    (pages, kv, page_size) — half the pool payload per slot, so the same
    pool memory holds ~2x the rows; decode dequantizes inside the kernel
    (see docs/serving.md for the accuracy trade-off). SWA layers riding a
    paged cache keep their full-precision head-major ring (the
    window-bounded ring is small; quantizing it buys ~nothing)."""
    if cache_dtype not in (None, "int8"):
        raise ValueError(f"unknown cache_dtype: {cache_dtype!r}")
    if cache_dtype == "int8" and layout != "paged":
        raise ValueError(
            "cache_dtype='int8' requires layout='paged' (the contiguous "
            "layouts have no per-slot scale planes)")
    S = min(max_len, window) if window is not None else max_len
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    if layout == "paged" and window is None:
        nb = -(-max_len // page_size)
        pages = total_pages if total_pages is not None else 1 + batch * nb
        if cache_dtype == "int8":
            return {
                "kp": jnp.zeros((pages, kv, page_size, hd), dtype=jnp.int8),
                "vp": jnp.zeros((pages, kv, page_size, hd), dtype=jnp.int8),
                "ks": jnp.zeros((pages, kv, page_size), dtype=jnp.float32),
                "vs": jnp.zeros((pages, kv, page_size), dtype=jnp.float32),
                "pt": jnp.zeros((batch, nb), dtype=jnp.int32),
            }
        return {
            "kp": jnp.zeros((pages, kv, page_size, hd), dtype=dtype),
            "vp": jnp.zeros((pages, kv, page_size, hd), dtype=dtype),
            "pt": jnp.zeros((batch, nb), dtype=jnp.int32),
        }
    if layout in ("head", "paged"):
        return {
            "kh": jnp.zeros((batch, kv, S, hd), dtype=dtype),
            "vh": jnp.zeros((batch, kv, S, hd), dtype=dtype),
        }
    return {
        "k": jnp.zeros((batch, S, kv, hd), dtype=dtype),
        "v": jnp.zeros((batch, S, kv, hd), dtype=dtype),
    }


def _cache_kv(cache: Params) -> Tuple[jax.Array, jax.Array, bool]:
    """(k, v, head_major) for either cache layout."""
    if "kh" in cache:
        return cache["kh"], cache["vh"], True
    return cache["k"], cache["v"], False


def _cache_valid_mask(pos, S: int, *, ring: bool,
                      offsets: Optional[jax.Array]) -> jax.Array:
    """(B?, S) visibility of cache slots at query position ``pos``.

    Delegates to the SAME ``_slot_visibility`` predicate the flash-decode
    kernel and its blockwise lowering use, so the kernel and non-kernel
    decode masks cannot drift. ``pos`` is a scalar or a per-row (B,)
    vector. Slot ``s`` holds global position ``s`` (full cache) or
    ``pos - ((pos - s) mod S)`` (ring buffer); window membership is
    implied by the ring depth (S = min(max_len, window)). ``offsets`` adds
    the per-sequence left-pad bound for ragged prompts. Returns (S,) only
    for scalar ``pos`` with no offsets, (B, S) otherwise."""
    from repro.kernels.flash_decode import _slot_visibility
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim:
        pos = pos.reshape(-1, 1)                            # (B, 1)
    idx = jnp.arange(S) if (pos.ndim == 0 and offsets is None) \
        else jnp.arange(S)[None, :]
    return _slot_visibility(
        idx, pos, seq_k=S, window=None, ring=ring,
        offset=None if offsets is None else offsets[:, None])


def attention_decode(params: Params, cfg: ModelConfig, x: jax.Array,
                     cache: Params, pos: jax.Array, *,
                     window: Optional[int] = None,
                     offsets: Optional[jax.Array] = None,
                     use_kernels: bool = False) -> Tuple[jax.Array, Params]:
    """One-token decode. x: (B, 1, D); pos: scalar int32 (every row at the
    same index) or per-row (B,) int32 (continuous batching).

    ``offsets`` (B,) int32: per-sequence left-pad widths for ragged
    prompts — RoPE positions become ``pos - offsets[b]`` and cache slots
    before each sequence's first real token are masked.
    ``use_kernels=True`` routes the cache attention through the Pallas
    flash-decode kernel (native on a head-major or paged cache; a
    seq-major cache is transposed on the fly — correct but not the fast
    path). A paged cache (``kp``/``vp``/``pt``, see ``init_kv_cache``)
    writes this token's K/V into the page holding slot ``pos`` via the
    row's block table and attends by gather — a retired row whose table
    was zeroed writes harmlessly into the reserved trash page 0.

    Returns (y (B,1,D), new_cache).
    """
    B = x.shape[0]
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pos = jnp.asarray(pos, jnp.int32)
    vector_pos = pos.ndim > 0
    posb = jnp.broadcast_to(pos.reshape(-1), (B,))
    if offsets is None:
        positions = posb[:, None]
    else:
        positions = (posb - offsets)[:, None].astype(jnp.int32)
    # kernel paths fuse the query rotation into the decode kernel
    # (rope_theta below) — only the cached key still needs its write-time
    # rotation here; the non-kernel paths rotate both as before
    q, k, v = _project_qkv(params, cfg, x, positions, rope=not use_kernels)
    if use_kernels:
        k = apply_rope(k, positions, cfg.rope_theta)

    if "pt" in cache:                  # paged pool + per-row block tables
        from repro.kernels import ops as kops
        from repro.kernels.flash_decode import _slot_visibility
        kp, vp, pt = cache["kp"], cache["vp"], cache["pt"]
        quantized = "ks" in cache
        ps, NB = kp.shape[2], pt.shape[1]
        b_idx = jnp.arange(B)
        page = pt[b_idx, jnp.clip(posb // ps, 0, NB - 1)]   # (B,)
        if quantized:
            # per-slot symmetric int8: one f32 scale per (row, kv head),
            # chosen so the largest |component| maps to 127
            kw, vw = k[:, 0], v[:, 0]                       # (B, kv, hd)
            ksc = jnp.maximum(jnp.abs(kw).max(axis=-1), 1e-8) / 127.0
            vsc = jnp.maximum(jnp.abs(vw).max(axis=-1), 1e-8) / 127.0
            kq = jnp.clip(jnp.round(kw / ksc[..., None]),
                          -127, 127).astype(jnp.int8)
            vq = jnp.clip(jnp.round(vw / vsc[..., None]),
                          -127, 127).astype(jnp.int8)
            kp = kp.at[page, :, posb % ps].set(kq)
            vp = vp.at[page, :, posb % ps].set(vq)
            ks_ = cache["ks"].at[page, :, posb % ps].set(
                ksc.astype(jnp.float32))
            vs_ = cache["vs"].at[page, :, posb % ps].set(
                vsc.astype(jnp.float32))
            new_cache = {"kp": kp, "vp": vp, "ks": ks_, "vs": vs_, "pt": pt}
        else:
            kp = kp.at[page, :, posb % ps].set(k[:, 0].astype(kp.dtype))
            vp = vp.at[page, :, posb % ps].set(v[:, 0].astype(vp.dtype))
            new_cache = {"kp": kp, "vp": vp, "pt": pt}
        if use_kernels:
            if quantized:
                out = kops.flash_decode_paged(
                    q, kp, vp, pt, posb, window=window, offsets=offsets,
                    k_scale=new_cache["ks"], v_scale=new_cache["vs"],
                    rope_theta=cfg.rope_theta)
            else:
                out = kops.flash_decode_paged(
                    q, kp.astype(q.dtype), vp.astype(q.dtype), pt, posb,
                    window=window, offsets=offsets,
                    rope_theta=cfg.rope_theta)
        else:
            S = NB * ps
            kg = kp[pt].transpose(0, 2, 1, 3, 4).reshape(B, kv, S, hd)
            vg = vp[pt].transpose(0, 2, 1, 3, 4).reshape(B, kv, S, hd)
            if quantized:
                ksg = new_cache["ks"][pt].transpose(0, 2, 1, 3) \
                    .reshape(B, kv, S, 1)
                vsg = new_cache["vs"][pt].transpose(0, 2, 1, 3) \
                    .reshape(B, kv, S, 1)
                kg = (kg.astype(jnp.float32) * ksg).astype(q.dtype)
                vg = (vg.astype(jnp.float32) * vsg).astype(q.dtype)
            m = _slot_visibility(
                jnp.arange(S)[None, :], posb[:, None], seq_k=S,
                window=window, ring=False,
                offset=None if offsets is None else offsets[:, None])
            out = _sdpa_grouped(q, kg.swapaxes(1, 2).astype(q.dtype),
                                vg.swapaxes(1, 2).astype(q.dtype),
                                m[:, None, :])
        y = out.reshape(B, 1, h * hd) @ params["wo"].astype(x.dtype)
        return y, new_cache

    ck, cv, head_major = _cache_kv(cache)
    seq_ax = 2 if head_major else 1
    S = ck.shape[seq_ax]
    if vector_pos:
        slot_b = posb % S if window is not None else posb
        b_idx = jnp.arange(B)
        if head_major:
            ck = ck.at[b_idx, :, slot_b].set(k[:, 0].astype(ck.dtype))
            cv = cv.at[b_idx, :, slot_b].set(v[:, 0].astype(cv.dtype))
        else:
            ck = ck.at[b_idx, slot_b].set(k[:, 0].astype(ck.dtype))
            cv = cv.at[b_idx, slot_b].set(v[:, 0].astype(cv.dtype))
    else:
        slot = pos % S if window is not None else pos
        start = (0, 0, slot, 0) if head_major else (0, slot, 0, 0)
        kw = k.swapaxes(1, 2) if head_major else k
        vw = v.swapaxes(1, 2) if head_major else v
        ck = jax.lax.dynamic_update_slice(ck, kw.astype(ck.dtype), start)
        cv = jax.lax.dynamic_update_slice(cv, vw.astype(cv.dtype), start)
    new_cache = {"kh": ck, "vh": cv} if head_major else {"k": ck, "v": cv}
    ring = window is not None
    kernel_pos = posb if vector_pos else pos
    if use_kernels:
        from repro.kernels import ops as kops
        khm = ck if head_major else ck.swapaxes(1, 2)
        vhm = cv if head_major else cv.swapaxes(1, 2)
        out = kops.flash_decode(q, khm.astype(q.dtype), vhm.astype(q.dtype),
                                kernel_pos, window=window, ring=ring,
                                offsets=offsets, rope_theta=cfg.rope_theta)
    else:
        valid = _cache_valid_mask(kernel_pos, S, ring=ring, offsets=offsets)
        m = jnp.broadcast_to(valid[None, None, :] if valid.ndim == 1
                             else valid[:, None, :], (B, 1, S))
        ks = ck.swapaxes(1, 2) if head_major else ck
        vs = cv.swapaxes(1, 2) if head_major else cv
        out = _sdpa_grouped(q, ks.astype(q.dtype), vs.astype(q.dtype), m)
    y = out.reshape(B, 1, h * hd) @ params["wo"].astype(x.dtype)
    return y, new_cache


def attention_prefill(params: Params, cfg: ModelConfig, x: jax.Array,
                      positions: jax.Array, cache: Params, *,
                      window: Optional[int] = None,
                      offsets: Optional[jax.Array] = None,
                      use_kernels: bool = False
                      ) -> Tuple[jax.Array, Params]:
    """Fused prefill for one attention layer: full-sequence attention that
    also scatters every position's K/V into the decode cache in one pass.

    x: (B, P, D); positions: (B, P) RoPE positions (already offset for
    left-padded ragged prompts). Full caches receive tokens 0..P-1 at slots
    0..P-1; SWA ring caches keep the last ``min(P, ring)`` tokens at their
    ring slots ``t % ring``. Returns (y (B, P, D), filled cache).
    """
    B, P, _ = x.shape
    q, k, v = _project_qkv(params, cfg, x, positions)
    ck, cv, head_major = _cache_kv(cache)
    seq_ax = 2 if head_major else 1
    S = ck.shape[seq_ax]
    assert window is not None or P <= S, (P, S)

    def fill(c, t):
        if head_major:
            t = t.swapaxes(1, 2)
        if P <= S:
            return jax.lax.dynamic_update_slice(c, t.astype(c.dtype),
                                                (0, 0, 0, 0))
        # ring wrap: keep the last S tokens; token at global position g
        # lands at slot g % S, i.e. the (P - S)-rotated tail of the window
        tail = jax.lax.slice_in_dim(t, P - S, P, axis=seq_ax)
        return jnp.roll(tail, (P - S) % S, axis=seq_ax).astype(c.dtype)

    new_cache = {"kh": fill(ck, k), "vh": fill(cv, v)} if head_major \
        else {"k": fill(ck, k), "v": fill(cv, v)}

    if use_kernels:
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=True, window=window,
                                   kv_offsets=offsets)
    else:
        m = (window_mask(P, P, window) if window is not None
             else causal_mask(P, P))
        if offsets is not None:
            m = m[None] & (jnp.arange(P)[None, None, :]
                           >= offsets[:, None, None])
        else:
            m = m[None]
        out = _sdpa(q, k, v, m)
    y = out.reshape(B, P, -1) @ params["wo"].astype(x.dtype)
    return y, new_cache


# -- cross attention ---------------------------------------------------------


def cross_attention_init(rng, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    return attention_init(rng, cfg, dtype)


def cross_kv(params: Params, cfg: ModelConfig, memory: jax.Array):
    """Project the (encoder / vision) memory once; reused across decode steps."""
    B, S, _ = memory.shape
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    dt = memory.dtype
    k = (memory @ params["wk"].astype(dt)).reshape(B, S, kv, hd)
    v = (memory @ params["wv"].astype(dt)).reshape(B, S, kv, hd)
    return k, v


def cross_attention_apply(params: Params, cfg: ModelConfig, x: jax.Array,
                          k: jax.Array, v: jax.Array) -> jax.Array:
    """x: (B,T,D) queries; k, v: projected memory (B,S,kv,hd)."""
    B, T, _ = x.shape
    h, hd = cfg.n_heads, cfg.head_dim
    dt = x.dtype
    q = (x @ params["wq"].astype(dt)).reshape(B, T, h, hd)
    if cfg.qk_norm:
        q = rmsnorm_apply(params["q_norm"], q, cfg.norm.eps)
    out = _sdpa(q, k.astype(dt), v.astype(dt), None)
    return out.reshape(B, T, h * hd) @ params["wo"].astype(dt)


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------


def mlp_init(rng, d: int, d_ff: int, dtype=jnp.float32) -> Params:
    ks = jax.random.split(rng, 3)
    return {
        "w_gate": dense_init(ks[0], (d, d_ff), dtype=dtype),
        "w_up": dense_init(ks[1], (d, d_ff), dtype=dtype),
        "w_down": dense_init(ks[2], (d_ff, d), dtype=dtype),
    }


def mlp_apply(params: Params, x: jax.Array,
              use_kernels: bool = False) -> jax.Array:
    dt = x.dtype
    hid = ("dp",) + (None,) * (x.ndim - 2) + ("model",)
    if use_kernels:
        from repro.kernels import ops as kops
        # fused gate GEMM + up GEMM + silu product, single saved hidden
        # activation (docs/kernels.md: swiglu)
        h = hint(kops.swiglu(x, params["w_gate"].astype(dt),
                             params["w_up"].astype(dt)), *hid)
        return h @ params["w_down"].astype(dt)
    g = hint(jax.nn.silu(x @ params["w_gate"].astype(dt)), *hid)
    u = hint(x @ params["w_up"].astype(dt), *hid)
    return (g * u) @ params["w_down"].astype(dt)
