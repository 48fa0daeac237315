"""Pallas TPU flash-decode: single-query-row attention against a
seq_len-deep KV cache — the serving hot path (decode_32k / long_500k).

One new token per sequence attends every cached key: there is no q-block
axis to tile, so the kernel streams KV blocks under an online-softmax
accumulator exactly like the training flash forward, but with a (g, hd)
query tile per kv head (g = H // KV, the GQA group — all q-heads that share
a kv head are processed together, so K/V blocks are read once per kv head).

Grid: (B, KV, n_kv_blocks) — the kv-block axis is innermost, so the running
max / normalizer / output accumulator live in VMEM scratch across kv steps
and the output tile is written once on the final step. The current position
``pos`` and the optional per-sequence left-pad ``offsets`` are dynamic
**per-row (B,) SMEM refs** (a scalar ``pos`` is broadcast): every sequence
in the batch may sit at a different depth — the continuous-batching engine's
rows do — and blocks entirely beyond that row's ``pos`` are skipped with
``pl.when``; at position p only ceil((p+1)/block_k) of the cache's
n_kv_blocks are touched, which is what makes the seq_len-deep cache
affordable early in the sequence.

Cache layouts:

- full attention: head-major ``(B, KV, S, hd)`` where slot ``s`` holds
  global position ``s`` (``ring=False``);
- sliding-window: the same shape but a ring buffer of ``S = min(max_len,
  window)`` slots where slot ``s`` holds global position
  ``pos - ((pos - s) mod S)`` (``ring=True``) — the slot->position map is
  evaluated inside the kernel so masking works pre- and post-wrap.

Visibility of a slot with global position g:  ``0 <= g <= pos``, and
``g > pos - window`` when a window is given, and ``g >= offsets[b]`` for
left-padded ragged prompts.

Serving is forward-only: there is no backward kernel (decode takes no
gradients). Public entry: :func:`repro.kernels.ops.flash_decode`; oracle:
:func:`repro.kernels.ref.flash_decode_ref`.

Off TPU, :func:`flash_decode_blockwise` is the serving lowering: the SAME
blockwise online-softmax program as a ``lax.scan`` over KV blocks.
Interpret-mode ``pallas_call`` pays a per-grid-step emulation cost
proportional to the full operand size — on a seq_len-deep cache that is
exactly the cost the kernel exists to avoid, so the hot serving path does
not run it (the kernel itself is validated against the oracle via
``interpret=True`` in tests/test_serving.py).

**Paged cache** (:func:`flash_decode_paged_pallas` /
:func:`flash_decode_paged_blockwise`): K/V live in a pool of fixed-size
pages ``(n_pages, KV, page_size, hd)`` and each row owns a block table
``pt (B, n_blocks)`` mapping its logical block i (slots
[i*page_size, (i+1)*page_size)) to a physical page. The kernel gathers by
block table via scalar-prefetch index maps (the page id picks the k/v
block to DMA); the blockwise lowering gathers one page per scan step —
neither ever materialises a row's cache contiguously. Visibility is the
same ``_slot_visibility`` predicate over logical slot indices, so a paged
row is bit-identical to the contiguous layout (fully-masked pages are
exact no-ops under the online softmax). Long-context rows then reserve
pages as they grow instead of worst-case contiguous memory.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import (NEG_INF, _rope_rotate,
                                           _rope_rotate_hm, _round_up,
                                           _sublane)

DEFAULT_BLOCK_K = 512


def _slot_visibility(slot, pos, *, seq_k: int, window: Optional[int],
                     ring: bool, offset=None):
    """Visibility of cache slots at query position ``pos`` — the ONE
    predicate shared by the Pallas kernel body, the blockwise CPU lowering,
    and (in spirit) the jnp oracle. ``slot`` is an int32 array of slot
    indices; ``offset`` an optional broadcastable left-pad bound."""
    if ring:
        gpos = pos - jnp.mod(pos - slot, seq_k)
    else:
        gpos = slot
    mask = (slot < seq_k) & (gpos >= 0) & (gpos <= pos)
    if window is not None:
        mask &= gpos > pos - window
    if offset is not None:
        mask = mask & (gpos >= offset)
    return mask


def _flash_decode_kernel(pos_ref, off_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, scale: float,
                         window: Optional[int], ring: bool, seq_k: int,
                         block_k: int, has_offsets: bool,
                         rope_theta: Optional[float] = None):
    b = pl.program_id(0)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    pos = pos_ref[b]
    k_start = ki * block_k
    # dynamic block skip: a full-layout block is dead if its first slot is
    # beyond pos (causal) or its last slot is older than the window. Ring
    # slots have no monotone slot->position map, so ring never skips (the
    # ring is at most window slots deep anyway).
    if ring:
        needed = jnp.bool_(True)
    else:
        needed = k_start <= pos
        if window is not None:
            needed = jnp.logical_and(needed,
                                     k_start + block_k - 1 > pos - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)               # (g, hd)
        if rope_theta is not None:
            # cached keys are rotated at write time; only the fresh query
            # row still needs its rotation — fused here, by the row's
            # logical position (pos minus any left pad)
            qpos = pos - (off_ref[b] if has_offsets else 0)
            q = _rope_rotate(
                q, jnp.zeros((q.shape[0], 1), jnp.float32) + qpos,
                rope_theta)
        q = q * scale
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = q @ k.T                                       # (g, bk)
        slot = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = _slot_visibility(
            slot, pos, seq_k=seq_k, window=window, ring=ring,
            offset=off_ref[b] if has_offsets else None)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                               # (g, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + p @ v
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_decode_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                        pos: jax.Array, *, window: Optional[int] = None,
                        ring: bool = False,
                        offsets: Optional[jax.Array] = None,
                        rope_theta: Optional[float] = None,
                        block_k: int = DEFAULT_BLOCK_K,
                        interpret: bool = False) -> jax.Array:
    """q: (B, H, hd); k, v: (B, KV, S, hd) head-major cache -> (B, H, hd).

    ``pos`` is the (dynamic) global position of each row's query token —
    a scalar (every row at the same depth, the static-batch engine) or a
    ``(B,)`` vector (continuous batching: one depth per row). Slots whose
    global position falls outside [max(offset, pos_b-window+1), pos_b] are
    masked, where the slot->position map is the identity (``ring=False``) or
    the ring-buffer map (``ring=True``, S = ring depth). ``offsets`` (B,)
    masks the left padding of ragged prompts.

    ``rope_theta`` fuses the query's RoPE rotation (by ``pos - offset``)
    into the kernel — q arrives UNROTATED; cached keys are rotated at
    write time as before.
    """
    B, H, hd = q.shape
    KV, S = k.shape[1], k.shape[2]
    g = H // KV
    sub = max(_sublane(q.dtype), _sublane(k.dtype))
    bk = _round_up(min(block_k, max(S, sub)), sub)
    Sp = _round_up(S, bk)
    if Sp != S:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
    qg = q.reshape(B, KV, g, hd)
    # per-row (B,) vectors held whole in SMEM (the kernel reads row b's
    # scalar); a scalar pos broadcasts to every row
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    has_offsets = offsets is not None
    off_arr = (jnp.asarray(offsets, jnp.int32).reshape(B) if has_offsets
               else jnp.zeros((1,), jnp.int32))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    out = pl.pallas_call(
        functools.partial(
            _flash_decode_kernel, scale=1.0 / math.sqrt(hd), window=window,
            ring=ring, seq_k=S, block_k=bk, has_offsets=has_offsets,
            rope_theta=rope_theta),
        name="flash_decode_fwd",
        grid=(B, KV, Sp // bk),
        in_specs=[
            smem,
            smem,
            pl.BlockSpec((1, 1, g, hd), lambda b, h, ki: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, ki: (b, h, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd), lambda b, h, ki: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, g, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, hd), jnp.float32),   # output accumulator
            pltpu.VMEM((g, 1), jnp.float32),    # running max
            pltpu.VMEM((g, 1), jnp.float32),    # running normalizer
        ],
        interpret=interpret,
    )(pos_arr, off_arr, qg, k, v)
    return out.reshape(B, H, hd)


def flash_decode_blockwise(q: jax.Array, k: jax.Array, v: jax.Array,
                           pos: jax.Array, *, window: Optional[int] = None,
                           ring: bool = False,
                           offsets: Optional[jax.Array] = None,
                           rope_theta: Optional[float] = None,
                           block_k: int = 2048) -> jax.Array:
    """Pure-jnp lowering of the same blockwise online-softmax program the
    Pallas kernel runs: a ``lax.scan`` over KV blocks carrying (m, l, acc),
    with the identical :func:`_slot_visibility` predicate. The off-TPU
    serving path (see module docstring)."""
    B, H, hd = q.shape
    KV, S = k.shape[1], k.shape[2]
    g = H // KV
    if rope_theta is not None:
        qpos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
        if offsets is not None:
            qpos = qpos - jnp.asarray(offsets, jnp.int32).reshape(-1)
        q = _rope_rotate_hm(q[:, :, None, :],
                            jnp.broadcast_to(qpos[:, None], (B, 1)),
                            rope_theta)[:, :, 0, :]
    bk = min(block_k, S)
    Sp = _round_up(S, bk)
    if Sp != S:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
    nk = Sp // bk
    qg = (q.astype(jnp.float32).reshape(B, KV, g, hd)
          * (1.0 / math.sqrt(hd)))
    kb = k.reshape(B, KV, nk, bk, hd).swapaxes(0, 2).swapaxes(1, 2)
    vb = v.reshape(B, KV, nk, bk, hd).swapaxes(0, 2).swapaxes(1, 2)
    off = None if offsets is None else offsets[:, None, None, None]
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim:                      # per-row (B,) -> broadcast over heads
        pos = pos.reshape(B, 1, 1, 1)

    def body(carry, inp):
        m, l, acc = carry
        kblk, vblk, ki = inp                              # (B, KV, bk, hd)
        s = jnp.einsum("bkgd,bksd->bkgs", qg, kblk.astype(jnp.float32))
        slot = ki * bk + jnp.arange(bk)
        mask = _slot_visibility(slot[None, None, None, :], pos, seq_k=S,
                                window=window, ring=ring, offset=off)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = alpha * l + p.sum(-1)
        acc = (alpha[..., None] * acc
               + jnp.einsum("bkgs,bksd->bkgd", p, vblk.astype(jnp.float32)))
        return (m_new, l, acc), None

    init = (jnp.full((B, KV, g), NEG_INF, jnp.float32),
            jnp.zeros((B, KV, g), jnp.float32),
            jnp.zeros((B, KV, g, hd), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(body, init,
                                  (kb, vb, jnp.arange(nk)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# paged cache (block-table gather)
# ---------------------------------------------------------------------------


def _flash_decode_paged_kernel(pt_ref, pos_ref, off_ref, q_ref, k_ref, v_ref,
                               *rest, scale: float,
                               window: Optional[int], page_size: int,
                               n_blocks: int, has_offsets: bool,
                               quantized: bool = False,
                               rope_theta: Optional[float] = None):
    rest = list(rest)
    ks_ref = vs_ref = None
    if quantized:
        ks_ref = rest.pop(0)
        vs_ref = rest.pop(0)
    o_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    i = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    pos = pos_ref[b]
    k_start = i * page_size
    # logical pages are monotone in position (no ring), so a page whose
    # first slot is beyond pos, or whose last slot predates the window, is
    # skipped. The DMA itself still lands on a valid physical page — an
    # unallocated logical block's table entry is the reserved trash page 0.
    needed = k_start <= pos
    if window is not None:
        needed = jnp.logical_and(needed,
                                 k_start + page_size - 1 > pos - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)               # (g, hd)
        if rope_theta is not None:
            qpos = pos - (off_ref[b] if has_offsets else 0)
            q = _rope_rotate(
                q, jnp.zeros((q.shape[0], 1), jnp.float32) + qpos,
                rope_theta)
        q = q * scale
        k = k_ref[0, 0].astype(jnp.float32)               # (ps, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        if quantized:
            # per-slot scales (ps, 1) broadcast over hd: int8 pages
            # dequantize in VMEM, right at the load
            k = k * ks_ref[0, 0]
            v = v * vs_ref[0, 0]
        s = q @ k.T                                       # (g, ps)
        slot = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = _slot_visibility(
            slot, pos, seq_k=n_blocks * page_size, window=window,
            ring=False, offset=off_ref[b] if has_offsets else None)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                               # (g, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + p @ v
        m_ref[...] = m_new

    @pl.when(i == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_decode_paged_pallas(q: jax.Array, kp: jax.Array, vp: jax.Array,
                              pt: jax.Array, pos: jax.Array, *,
                              window: Optional[int] = None,
                              offsets: Optional[jax.Array] = None,
                              k_scale: Optional[jax.Array] = None,
                              v_scale: Optional[jax.Array] = None,
                              rope_theta: Optional[float] = None,
                              interpret: bool = False) -> jax.Array:
    """q: (B, H, hd); kp, vp: (n_pages, KV, page_size, hd) physical page
    pool; pt: (B, n_blocks) int32 block table -> (B, H, hd).

    Row b's logical slots [i*page_size, (i+1)*page_size) live in physical
    page ``pt[b, i]``. The block table, per-row ``pos`` and per-row
    ``offsets`` ride in as scalar-prefetch refs so the k/v BlockSpec index
    maps can pick the physical page to DMA per grid step — the gather IS
    the index map; no contiguous copy of the row's cache ever exists.
    Grid: (B, KV, n_blocks) with the page axis innermost (online softmax
    over logical pages in order). Ring buffers are not paged (SWA caches
    are window-bounded); ``ring`` is intentionally absent.

    ``k_scale``/``v_scale`` (n_pages, KV, page_size) f32 mark an int8 pool:
    kp/vp hold int8 codes and each slot's row dequantizes in VMEM right at
    the load (``k = kp * k_scale``), so the HBM traffic per page is half
    (plus the scale sidecar). ``rope_theta`` fuses the query rotation as in
    :func:`flash_decode_pallas`.
    """
    B, H, hd = q.shape
    n_pages, KV, ps = kp.shape[0], kp.shape[1], kp.shape[2]
    NB = pt.shape[1]
    g = H // KV
    qg = q.reshape(B, KV, g, hd)
    pt_arr = jnp.asarray(pt, jnp.int32)
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    has_offsets = offsets is not None
    off_arr = (jnp.asarray(offsets, jnp.int32).reshape(B) if has_offsets
               else jnp.zeros((B,), jnp.int32))
    quantized = k_scale is not None

    page_spec = pl.BlockSpec((1, 1, ps, hd),
                             lambda b, h, i, pt, pos, off: (pt[b, i], h, 0, 0))
    in_specs = [
        pl.BlockSpec((1, 1, g, hd),
                     lambda b, h, i, pt, pos, off: (b, h, 0, 0)),
        page_spec,
        page_spec,
    ]
    inputs = [qg, kp, vp]
    if quantized:
        # scales follow the same page gather; trailing unit axis keeps the
        # sublane-aligned page_size off the lane axis (see lse in the
        # training forward)
        scale_spec = pl.BlockSpec(
            (1, 1, ps, 1), lambda b, h, i, pt, pos, off: (pt[b, i], h, 0, 0))
        in_specs += [scale_spec, scale_spec]
        inputs += [k_scale.reshape(n_pages, KV, ps, 1),
                   v_scale.reshape(n_pages, KV, ps, 1)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KV, NB),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, g, hd),
                               lambda b, h, i, pt, pos, off: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, hd), jnp.float32),   # output accumulator
            pltpu.VMEM((g, 1), jnp.float32),    # running max
            pltpu.VMEM((g, 1), jnp.float32),    # running normalizer
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _flash_decode_paged_kernel, scale=1.0 / math.sqrt(hd),
            window=window, page_size=ps, n_blocks=NB,
            has_offsets=has_offsets, quantized=quantized,
            rope_theta=rope_theta),
        name="flash_decode_paged_fwd",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, g, hd), q.dtype),
        interpret=interpret,
    )(pt_arr, pos_arr, off_arr, *inputs)
    return out.reshape(B, H, hd)


def flash_decode_paged_blockwise(q: jax.Array, kp: jax.Array, vp: jax.Array,
                                 pt: jax.Array, pos: jax.Array, *,
                                 window: Optional[int] = None,
                                 offsets: Optional[jax.Array] = None,
                                 k_scale: Optional[jax.Array] = None,
                                 v_scale: Optional[jax.Array] = None,
                                 rope_theta: Optional[float] = None
                                 ) -> jax.Array:
    """Pure-jnp lowering of the paged kernel: a ``lax.scan`` over logical
    blocks, gathering ONE page per row per step (``kp[pt[:, i]]``) under the
    same online-softmax carry and :func:`_slot_visibility` predicate. The
    off-TPU serving path for paged caches — peak memory per step is one
    page per row, never the full gathered cache. ``k_scale``/``v_scale``
    mark an int8 pool (dequantized per gathered page); ``rope_theta`` fuses
    the query rotation."""
    B, H, hd = q.shape
    KV, ps = kp.shape[1], kp.shape[2]
    NB = pt.shape[1]
    g = H // KV
    if rope_theta is not None:
        qpos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
        if offsets is not None:
            qpos = qpos - jnp.asarray(offsets, jnp.int32).reshape(-1)
        q = _rope_rotate_hm(q[:, :, None, :],
                            jnp.broadcast_to(qpos[:, None], (B, 1)),
                            rope_theta)[:, :, 0, :]
    qg = (q.astype(jnp.float32).reshape(B, KV, g, hd)
          * (1.0 / math.sqrt(hd)))
    off = None if offsets is None else offsets[:, None, None, None]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1),
                           (B,)).reshape(B, 1, 1, 1)

    def body(carry, inp):
        m, l, acc = carry
        page_ids, i = inp                              # (B,), ()
        kblk = kp[page_ids].astype(jnp.float32)        # (B, KV, ps, hd)
        vblk = vp[page_ids].astype(jnp.float32)
        if k_scale is not None:
            kblk = kblk * k_scale[page_ids][..., None]
            vblk = vblk * v_scale[page_ids][..., None]
        s = jnp.einsum("bkgd,bksd->bkgs", qg, kblk)
        slot = i * ps + jnp.arange(ps)
        mask = _slot_visibility(slot[None, None, None, :], pos,
                                seq_k=NB * ps, window=window, ring=False,
                                offset=off)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = alpha * l + p.sum(-1)
        acc = (alpha[..., None] * acc
               + jnp.einsum("bkgs,bksd->bkgd", p, vblk))
        return (m_new, l, acc), None

    init = (jnp.full((B, KV, g), NEG_INF, jnp.float32),
            jnp.zeros((B, KV, g), jnp.float32),
            jnp.zeros((B, KV, g, hd), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(
        body, init, (jnp.asarray(pt, jnp.int32).T, jnp.arange(NB)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, H, hd).astype(q.dtype)
