"""Pallas TPU flash attention (streaming softmax), with causal masking,
sliding-window support, GQA, and a dedicated Pallas backward.

TPU-native design: the forward grid is (B, H, n_q_blocks, n_kv_blocks) — TPU
iterates the last grid axis sequentially per core, so the running max /
normalizer / accumulator live in VMEM scratch across kv steps and the output
block is written once on the final kv step. KV blocks that are entirely
masked (beyond causal frontier or older than the window) are skipped with
``pl.when``. Block sizes are sublane-aligned (rounded up to the dtype's
sublane multiple — 8 for f32, 16 for bf16 — so ragged ``T``/``S`` produce
legal BlockSpecs outside interpret mode); GQA indexes the kv head as
h // (H // KV) in the BlockSpec index maps, so K/V are never materialised
per-q-head.

Backward: the standard recomputation trick. The forward additionally emits
the per-row logsumexp ``lse = m + log l`` (the only residual beyond the
inputs and output), and the backward recomputes the probabilities
``p = exp(q k^T * scale - lse)`` blockwise instead of storing the (T, S)
matrix:

- ``_flash_bwd_dq_kernel`` — grid (B, H, n_q, n_kv), kv innermost; dq is
  accumulated in VMEM scratch across kv steps and written once.
- ``_flash_bwd_dkv_kernel`` — the transposed grid (B, H, n_kv, n_q), q
  innermost; dk and dv accumulate in VMEM scratch across q steps. Gradients
  are produced per q-head; :func:`flash_attention_backward_pallas` sums the
  GQA cotangents over each q-head group outside the kernel.

Both backward kernels skip non-intersecting (q-block, kv-block) pairs with
the same visibility test as the forward.

Layout: q (B, H, T, hd); k, v (B, KV, S, hd) — head-major so the sequence
axis is the penultimate (sublane) dimension of each block.

RoPE (:func:`flash_attention_rope_pallas`): q and k arrive unrotated and
are rotated once per call, in XLA, into f32 head-major tensors that the
same forward kernel reads; the kernel itself evaluates no angle. The
backward rotates the saved unrotated q/k the same way and un-rotates dq/dk.

Public entry: :func:`repro.kernels.ops.flash_attention` (differentiable via
``jax.custom_vjp``). Oracles: :func:`repro.kernels.ref.attention_ref` /
:func:`repro.kernels.ref.attention_vjp_ref`.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _sublane(dtype) -> int:
    """Minimum sublane multiple for a block's penultimate axis."""
    return 16 if jnp.dtype(dtype) == jnp.bfloat16 else 8


def _round_up(n: int, mult: int) -> int:
    return (n + mult - 1) // mult * mult


def _block_sizes(T: int, S: int, block_q: int, block_k: int,
                 dtype) -> Tuple[int, int]:
    """Sublane-aligned (bq, bk): never larger than the padded sequence, and
    always a multiple of the dtype's sublane count, so the BlockSpecs are
    legal on hardware even for ragged ``T``/``S`` (e.g. T=100 -> bq=104,
    not 100)."""
    sub = _sublane(dtype)
    bq = _round_up(min(block_q, max(T, sub)), sub)
    bk = _round_up(min(block_k, max(S, sub)), sub)
    return bq, bk


def _band_intersects(q_start, k_start, *, causal: bool,
                     window: Optional[int], block_q: int, block_k: int):
    """Does this (q-block, kv-block) pair intersect the visible band?
    Shared by the forward and both backward kernels so they agree on which
    blocks are skipped."""
    needed = True
    if causal:
        needed = k_start <= q_start + block_q - 1
    if window is not None:
        # newest visible key for the oldest query in the block:
        needed = jnp.logical_and(
            needed, k_start + block_k - 1 > q_start - window)
    return needed


def _rope_rotate(x, pos, theta: float):
    """Half-rotation RoPE on one f32 (rows, hd) tile with per-row positions
    ``pos`` (rows, 1) f32 — the in-kernel form of ``layers.apply_rope``
    (llama convention, ``freqs_i = theta ** -(i / (hd/2))``). Shared by
    both decode kernels, which rotate one query row per step with it, so
    the rotation cannot drift between them."""
    hd = x.shape[-1]
    half = hd // 2
    # Mosaic has no float iota: generate int32 and convert
    j = jax.lax.broadcasted_iota(jnp.int32, (1, half), 1).astype(jnp.float32)
    ang = pos * jnp.exp(-(j / half) * math.log(theta))    # (rows, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[:, :half], x[:, half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


def _visibility_mask(s_shape, q_start, k_start, *, causal: bool,
                     window: Optional[int], seq_k: int, kv_offset=None):
    q_idx = q_start + jax.lax.broadcasted_iota(jnp.int32, s_shape, 0)
    k_idx = k_start + jax.lax.broadcasted_iota(jnp.int32, s_shape, 1)
    mask = k_idx < seq_k
    if causal:
        mask &= k_idx <= q_idx
    if window is not None:
        mask &= k_idx > q_idx - window
    if kv_offset is not None:
        # left-padded ragged prefill: keys before this sequence's first real
        # token are invisible (dynamic per-batch scalar)
        mask &= k_idx >= kv_offset
    return mask


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _flash_kernel(q_ref, k_ref, v_ref, *rest, scale: float, causal: bool,
                  window: Optional[int], block_q: int, block_k: int,
                  seq_k: int, has_offsets: bool = False):
    rest = list(rest)
    off_ref = rest.pop(0) if has_offsets else None
    o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = ki * block_k
    needed = _band_intersects(q_start, k_start, causal=causal, window=window,
                              block_q=block_q, block_k=block_k)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)               # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        q = q * scale
        s = q @ k.T                                       # (bq, bk)
        mask = _visibility_mask(
            s.shape, q_start, k_start, causal=causal, window=window,
            seq_k=seq_k,
            kv_offset=off_ref[b] if has_offsets else None)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                               # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + p @ v
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(l)           # (bq, 1)


def _forward_call(q: jax.Array, k: jax.Array, v: jax.Array,
                  kv_offsets: Optional[jax.Array], *, causal: bool,
                  window: Optional[int], block_q: int, block_k: int,
                  out_dtype):
    """Everything the forward's ``pallas_call`` takes but its name: the
    kernel, its keyword arguments and its (padded) inputs. Blocks are
    aligned to the sublanes of the narrowest of q/k/v, so f32 q/k beside a
    bf16 v tile as the bf16 inputs would."""
    B, H, T, hd = q.shape
    KV, S = k.shape[1], k.shape[2]
    g = H // KV
    narrowest = min((q.dtype, k.dtype, v.dtype),
                    key=lambda d: jnp.dtype(d).itemsize)
    bq, bk = _block_sizes(T, S, block_q, block_k, narrowest)
    Tp, Sp = _round_up(T, bq), _round_up(S, bk)
    if Tp != T:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
    if Sp != S:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))

    has_offsets = kv_offsets is not None
    inputs = (q, k, v)
    off_specs = []
    if has_offsets:
        # the whole (B,) vector sits in SMEM; the kernel reads its row's
        # scalar (a (1,) block of a 1-D SMEM array is not a legal tile)
        inputs = inputs + (jnp.asarray(kv_offsets, jnp.int32).reshape(B),)
        off_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)]

    kernel = functools.partial(
        _flash_kernel, scale=1.0 / math.sqrt(hd), causal=causal,
        window=window, block_q=bq, block_k=bk, seq_k=S,
        has_offsets=has_offsets)
    call = dict(
        grid=(B, H, Tp // bq, Sp // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, qi, ki: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, qi, ki: (b, h // g, ki, 0)),
        ] + off_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            # trailing unit axis keeps bq on the SUBLANE axis — a (1,1,bq)
            # block would put the merely-sublane-aligned bq on the lane
            # axis, which is illegal off-interpret for ragged T
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tp, hd), out_dtype),
            jax.ShapeDtypeStruct((B, H, Tp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),   # acc
            pltpu.VMEM((bq, 1), jnp.float32),    # running max
            pltpu.VMEM((bq, 1), jnp.float32),    # running normalizer
        ],
    )
    return kernel, call, inputs


def _trim(out: jax.Array, lse: jax.Array, T: int, return_residuals: bool):
    if return_residuals:
        return out[:, :, :T], lse[:, :, :T, 0]
    return out[:, :, :T]


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True,
                           window: Optional[int] = None,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K,
                           return_residuals: bool = False,
                           kv_offsets: Optional[jax.Array] = None,
                           interpret: bool = False
                           ) -> Union[jax.Array,
                                      Tuple[jax.Array, jax.Array]]:
    """q: (B, H, T, hd); k, v: (B, KV, S, hd) -> (B, H, T, hd).

    ``return_residuals=True`` additionally returns the per-row logsumexp
    ``lse`` (B, H, T) f32 — the residual the backward pass needs to
    recompute the probabilities blockwise.

    ``kv_offsets`` (B,) int32 hides keys before each sequence's first real
    token (left-padded ragged prefill). Forward-only: the serving fused
    prefill uses it; the differentiable training entry does not expose it.
    """
    kernel, call, inputs = _forward_call(
        q, k, v, kv_offsets, causal=causal, window=window, block_q=block_q,
        block_k=block_k, out_dtype=q.dtype)
    out, lse = pl.pallas_call(kernel, name="flash_attention_fwd",
                              interpret=interpret, **call)(*inputs)
    return _trim(out, lse, q.shape[2], return_residuals)


def _rope_rotate_hm(x: jax.Array, pos: jax.Array, theta: float,
                    dtype=None) -> jax.Array:
    """Head-major RoPE: x (B, Hx, T, hd), pos (B, T) -> ``dtype`` (x.dtype
    when None), computed in f32. Same llama half-split convention as
    :func:`_rope_rotate`; negate ``pos`` to rotate back (the rotation is
    orthogonal)."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-(jnp.arange(half, dtype=jnp.float32) / half)
                    * math.log(theta))
    ang = pos.astype(jnp.float32)[:, None, :, None] * freqs   # (B, 1, T, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype if dtype is None else dtype)


def flash_attention_rope_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                                pos: jax.Array, *, theta: float,
                                causal: bool = True,
                                window: Optional[int] = None,
                                block_q: int = DEFAULT_BLOCK_Q,
                                block_k: int = DEFAULT_BLOCK_K,
                                return_residuals: bool = False,
                                kv_offsets: Optional[jax.Array] = None,
                                interpret: bool = False
                                ) -> Union[jax.Array,
                                           Tuple[jax.Array, jax.Array]]:
    """Flash attention over RoPE-rotated q and k, taking them unrotated.

    Same contract as :func:`flash_attention_pallas` plus ``pos`` (B, T)
    positions shared by q and k (self-attention: S == T required) and the
    static rotation base ``theta``; the output keeps q's dtype. q and k are
    rotated once per call, outside the kernel, into f32 head-major tensors
    (an elementwise XLA pass each), and the plain forward kernel runs on
    them: the rotated q/k reach the ``q @ k.T`` product in f32, and no
    angle, sine or cosine is evaluated per (q block, kv block) pair.
    Rotating each tile in the kernel instead recomputed every angle once
    per visible block pair (~2,200 times at 2048 tokens): on one TPU v5e at
    8 x 2048 tokens, 16/8 heads of 128, the forward took 37.3 ms that way
    and takes 14.9 ms this way (docs/kernels.md).
    """
    T, hd = q.shape[2], q.shape[3]
    if k.shape[2] != T:
        raise ValueError("fused-RoPE attention is self-attention only")
    if hd % 2:
        raise ValueError("RoPE needs an even head dim")
    kernel, call, inputs = _forward_call(
        _rope_rotate_hm(q, pos, theta, jnp.float32),
        _rope_rotate_hm(k, pos, theta, jnp.float32), v, kv_offsets,
        causal=causal, window=window, block_q=block_q, block_k=block_k,
        out_dtype=q.dtype)
    out, lse = pl.pallas_call(kernel, name="flash_attention_rope_fwd",
                              interpret=interpret, **call)(*inputs)
    return _trim(out, lse, T, return_residuals)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _recompute_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    q_start, k_start, *, scale: float, causal: bool,
                    window: Optional[int], seq_k: int):
    """Shared recomputation for both backward kernels: rebuild this block's
    probabilities from the lse residual and form ``ds = p * (dp - delta)``
    (the softmax-backward core). Keeping it in one place keeps the dq and
    dk/dv kernels' masking/scaling in lockstep. Returns (q, k, do, p, ds),
    all f32."""
    q = q_ref[0, 0].astype(jnp.float32)                   # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)                   # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]                                   # (bq, 1)
    delta = delta_ref[0, 0]                               # (bq, 1)
    s = (q @ k.T) * scale                                 # (bq, bk)
    mask = _visibility_mask(s.shape, q_start, k_start, causal=causal,
                            window=window, seq_k=seq_k)
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse)
    dp = do @ v.T                                         # (bq, bk)
    ds = p * (dp - delta)
    return q, k, do, p, ds


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_acc, *, scale: float, causal: bool,
                         window: Optional[int], block_q: int, block_k: int,
                         seq_k: int):
    """dq for one q block, accumulated across kv blocks (innermost axis)."""
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q_start = qi * block_q
    k_start = ki * block_k
    needed = _band_intersects(q_start, k_start, causal=causal, window=window,
                              block_q=block_q, block_k=block_k)

    @pl.when(needed)
    def _compute():
        _, k, _, _, ds = _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, q_start,
            k_start, scale=scale, causal=causal, window=window, seq_k=seq_k)
        dq_acc[...] += (ds @ k) * scale

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                          causal: bool, window: Optional[int], block_q: int,
                          block_k: int, seq_k: int):
    """Per-q-head dk/dv for one kv block, accumulated across q blocks
    (innermost axis). GQA groups are summed outside the kernel."""
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start = qi * block_q
    k_start = ki * block_k
    needed = _band_intersects(q_start, k_start, causal=causal, window=window,
                              block_q=block_q, block_k=block_k)

    @pl.when(needed)
    def _compute():
        q, _, do, p, ds = _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, q_start,
            k_start, scale=scale, causal=causal, window=window, seq_k=seq_k)
        dv_acc[...] += p.T @ do                           # (bk, hd)
        dk_acc[...] += (ds.T @ q) * scale

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


# dq rides as a full (1, 1, Tp, hd) output block in the fused backward; cap
# its VMEM footprint (acc itemsize * Tp * hd) or fall back to the two-kernel
# path
_FUSED_BWD_DQ_VMEM_BYTES = 1 << 21


def _flash_bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                            dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                            scale: float, causal: bool,
                            window: Optional[int], block_q: int,
                            block_k: int, seq_k: int):
    """One recomputation feeding BOTH accumulators. Grid (B, H, n_kv, n_q),
    q innermost: dk/dv accumulate in VMEM scratch exactly as in
    ``_flash_bwd_dkv_kernel``, while dq accumulates into a full-(Tp, hd)
    output block whose index map is constant over (ki, qi) — the block is
    resident in VMEM for the whole (b, h) sweep (consecutive revisits), so
    each (q, kv) pair's ``p``/``ds`` recompute — the expensive half of the
    backward — happens once instead of twice."""
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(jnp.logical_and(ki == 0, qi == 0))
    def _init_dq():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    @pl.when(qi == 0)
    def _init_kv():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start = qi * block_q
    k_start = ki * block_k
    needed = _band_intersects(q_start, k_start, causal=causal, window=window,
                              block_q=block_q, block_k=block_k)

    @pl.when(needed)
    def _compute():
        q, k, do, p, ds = _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, q_start,
            k_start, scale=scale, causal=causal, window=window, seq_k=seq_k)
        dv_acc[...] += (p.T @ do).astype(dv_acc.dtype)
        dk_acc[...] += ((ds.T @ q) * scale).astype(dk_acc.dtype)
        dq_ref[0, 0, pl.ds(q_start, block_q), :] += (
            (ds @ k) * scale).astype(dq_ref.dtype)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_backward_pallas(
        q: jax.Array, k: jax.Array, v: jax.Array, o: jax.Array,
        lse: jax.Array, do: jax.Array, *, causal: bool = True,
        window: Optional[int] = None, block_q: int = DEFAULT_BLOCK_Q,
        block_k: int = DEFAULT_BLOCK_K, fuse_dq: Optional[bool] = None,
        acc_dtype=jnp.float32, interpret: bool = False
        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """VJP of :func:`flash_attention_pallas` w.r.t. (q, k, v).

    q, o, do: (B, H, T, hd); k, v: (B, KV, S, hd); lse: (B, H, T) f32 (the
    forward's logsumexp residual). Returns (dq, dk, dv) in the input dtypes.

    Standard recomputation backward: ``delta = rowsum(do * o)`` is one cheap
    elementwise pass outside the kernels; the probability blocks are rebuilt
    from ``lse`` inside each kernel, so no (T, S)-sized tensor is ever
    materialised.

    ``fuse_dq=None`` (auto) picks the single-kernel fused path — one
    ``p``/``ds`` recompute feeding dq AND dk/dv — whenever the full dq block
    (``Tp * hd`` in ``acc_dtype``) fits the VMEM budget, else the original
    two-kernel split (which recomputes each block pair twice).
    ``acc_dtype`` sets the fused path's accumulator precision (the bf16
    accumulation study in docs/kernels.md uses ``jnp.bfloat16`` here).
    """
    B, H, T, hd = q.shape
    KV, S = k.shape[1], k.shape[2]
    g = H // KV
    scale = 1.0 / math.sqrt(hd)
    bq, bk = _block_sizes(T, S, block_q, block_k, q.dtype)
    Tp, Sp = _round_up(T, bq), _round_up(S, bk)

    # per-row terms carry a trailing unit axis so bq stays on the sublane
    # axis of their blocks (see the forward's lse out_spec)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[..., None]
    lse = lse[..., None]
    if Tp != T:
        pad_t = ((0, 0), (0, 0), (0, Tp - T), (0, 0))
        q = jnp.pad(q, pad_t)
        do = jnp.pad(do, pad_t)
        lse = jnp.pad(lse, pad_t)
        delta = jnp.pad(delta, pad_t)
    if Sp != S:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))

    # transposed grid: kv blocks outer, q blocks innermost so the dk/dv
    # accumulators persist in VMEM across q steps
    qT_spec = pl.BlockSpec((1, 1, bq, hd), lambda b, h, ki, qi: (b, h, qi, 0))
    kvT_spec = pl.BlockSpec((1, 1, bk, hd),
                            lambda b, h, ki, qi: (b, h // g, ki, 0))
    rowT_spec = pl.BlockSpec((1, 1, bq, 1),
                             lambda b, h, ki, qi: (b, h, qi, 0))
    dkvT_spec = pl.BlockSpec((1, 1, bk, hd),
                             lambda b, h, ki, qi: (b, h, ki, 0))

    if fuse_dq is None:
        fuse_dq = (Tp * hd * jnp.dtype(acc_dtype).itemsize
                   <= _FUSED_BWD_DQ_VMEM_BYTES)

    if fuse_dq:
        dq_full_spec = pl.BlockSpec((1, 1, Tp, hd),
                                    lambda b, h, ki, qi: (b, h, 0, 0))
        dqh, dkh, dvh = pl.pallas_call(
            functools.partial(
                _flash_bwd_fused_kernel, scale=scale, causal=causal,
                window=window, block_q=bq, block_k=bk, seq_k=S),
            name="flash_attention_bwd_fused",
            grid=(B, H, Sp // bk, Tp // bq),
            in_specs=[qT_spec, kvT_spec, kvT_spec, qT_spec, rowT_spec,
                      rowT_spec],
            out_specs=[dq_full_spec, dkvT_spec, dkvT_spec],
            out_shape=[jax.ShapeDtypeStruct((B, H, Tp, hd), acc_dtype),
                       jax.ShapeDtypeStruct((B, H, Sp, hd), acc_dtype),
                       jax.ShapeDtypeStruct((B, H, Sp, hd), acc_dtype)],
            scratch_shapes=[pltpu.VMEM((bk, hd), acc_dtype),
                            pltpu.VMEM((bk, hd), acc_dtype)],
            interpret=interpret,
        )(q, k, v, do, lse, delta)
        dq = dqh.astype(q.dtype)
    else:
        q_spec = pl.BlockSpec((1, 1, bq, hd),
                              lambda b, h, qi, ki: (b, h, qi, 0))
        kv_spec = pl.BlockSpec((1, 1, bk, hd),
                               lambda b, h, qi, ki: (b, h // g, ki, 0))
        row_spec = pl.BlockSpec((1, 1, bq, 1),
                                lambda b, h, qi, ki: (b, h, qi, 0))

        dq = pl.pallas_call(
            functools.partial(
                _flash_bwd_dq_kernel, scale=scale, causal=causal,
                window=window, block_q=bq, block_k=bk, seq_k=S),
            name="flash_attention_bwd_dq",
            grid=(B, H, Tp // bq, Sp // bk),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
            out_specs=q_spec,
            out_shape=jax.ShapeDtypeStruct((B, H, Tp, hd), q.dtype),
            scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
            interpret=interpret,
        )(q, k, v, do, lse, delta)

        dkh, dvh = pl.pallas_call(
            functools.partial(
                _flash_bwd_dkv_kernel, scale=scale, causal=causal,
                window=window, block_q=bq, block_k=bk, seq_k=S),
            name="flash_attention_bwd_dkv",
            grid=(B, H, Sp // bk, Tp // bq),
            in_specs=[qT_spec, kvT_spec, kvT_spec, qT_spec, rowT_spec,
                      rowT_spec],
            out_specs=[dkvT_spec, dkvT_spec],
            out_shape=[jax.ShapeDtypeStruct((B, H, Sp, hd), jnp.float32),
                       jax.ShapeDtypeStruct((B, H, Sp, hd), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                            pltpu.VMEM((bk, hd), jnp.float32)],
            interpret=interpret,
        )(q, k, v, do, lse, delta)

    # GQA: sum the per-q-head cotangents over each q-head group
    dk = dkh.reshape(B, KV, g, Sp, hd).sum(axis=2)[:, :, :S].astype(k.dtype)
    dv = dvh.reshape(B, KV, g, Sp, hd).sum(axis=2)[:, :, :S].astype(v.dtype)
    return dq[:, :, :T], dk, dv


def flash_attention_rope_backward_pallas(
        q: jax.Array, k: jax.Array, v: jax.Array, pos: jax.Array,
        o: jax.Array, lse: jax.Array, do: jax.Array, *, theta: float,
        causal: bool = True, window: Optional[int] = None,
        block_q: int = DEFAULT_BLOCK_Q, block_k: int = DEFAULT_BLOCK_K,
        interpret: bool = False) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """VJP of :func:`flash_attention_rope_pallas` w.r.t. (q, k, v).

    The rotation is orthogonal and position-wise, so the chain rule factors
    cleanly around the shared backward kernels: rotate q/k by +theta once
    outside (a cheap elementwise recompute — the unrotated q/k are the saved
    residuals), run :func:`flash_attention_backward_pallas` on the rotated
    inputs, then rotate the resulting dq/dk back by -theta
    (``R(-theta) = R(theta)^T``). dv is untouched by RoPE.
    """
    qr = _rope_rotate_hm(q, pos, theta)
    kr = _rope_rotate_hm(k, pos, theta)
    dqr, dkr, dv = flash_attention_backward_pallas(
        qr, kr, v, o, lse, do, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret)
    dq = _rope_rotate_hm(dqr, -jnp.asarray(pos, jnp.float32), theta)
    dk = _rope_rotate_hm(dkr, -jnp.asarray(pos, jnp.float32), theta)
    return dq, dk, dv
