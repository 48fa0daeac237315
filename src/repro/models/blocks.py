"""LayerSpec interpreter: builds and applies transformer/ssm blocks.

A "block" is one LayerSpec: optional cross-attention sublayer, a sequence
mixer (attention / sliding-window attention / mamba), and a feed-forward
(dense SwiGLU / MoE / none), each with pre-norms and residuals.

The repeating ``body_pattern`` is executed as a ``lax.scan`` over stacked
parameters (one stack of ``body_repeats`` per pattern slot) so that HLO size
and compile time stay flat in network depth.

Each sublayer runs under a ``jax.named_scope`` (the HLO ``op_name``
metadata a device trace is read by, docs/observability.md): ``norm`` (the
pre-norms and the fused residual-add + norm, set in :mod:`.layers`),
``attention`` (projections, qk-norm, RoPE and the attention kernel or its
jnp twin, with the residual add where it is not fused into the next norm),
``ssm``, and ``mlp`` or ``moe`` (with their residual add).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.core import expert_parallel as EP
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.sharding.hints import current_mesh, hint

Params = Dict[str, Any]


def _tp_axis(local_dim: int, full_dim: int) -> Optional[str]:
    """Megatron-in-region detection: inside a manual shard_map region
    (:func:`EP.manual_mode`) a block may receive the LOCAL tensor-parallel
    slice of its weights. Sliced-ness is inferred from the actual leaf shape
    vs the config's full width — the same always-agrees-with-the-spec-builder
    trick as :func:`EP.manual_shard_mode` — and the model axis name is
    returned so the caller can fence the sublayer with the region_in /
    region_out adjoint pair."""
    st = EP.manual_state()
    if st is None or st[0] is None:
        return None
    return st[0] if local_dim != full_dim else None


def _sp_hint(x: jax.Array, enabled: bool) -> jax.Array:
    """Megatron-style sequence parallelism: between blocks the residual
    stream is sharded over ('model' x sequence) in addition to the batch
    axes, so remat-saved block inputs shrink by the model-parallel degree.
    GSPMD inserts the all-gather at the qkv/mlp projections and turns the
    output all-reduces into reduce-scatters."""
    if not enabled or x.ndim != 3:
        return x
    mesh = current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return x
    if x.shape[1] % mesh.shape["model"] != 0:
        return x
    return hint(x, "dp", "model", None)

ZERO_AUX = {"moe_aux": jnp.zeros(()), "moe_z": jnp.zeros(())}


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------


def block_init(rng, cfg: ModelConfig, spec: LayerSpec, dtype=jnp.float32
               ) -> Params:
    ks = jax.random.split(rng, 4)
    p: Params = {}
    if spec.mixer in ("attn", "swa"):
        p["norm1"] = L.norm_init(cfg, cfg.d_model, jnp.float32)
        p["mixer"] = L.attention_init(ks[0], cfg, dtype)
    elif spec.mixer == "ssm":
        p["norm1"] = L.norm_init(cfg, cfg.d_model, jnp.float32)
        p["mixer"] = SSM.ssm_init(ks[0], cfg, dtype)
    if spec.cross_attn:
        p["norm_x"] = L.norm_init(cfg, cfg.d_model, jnp.float32)
        p["cross"] = L.cross_attention_init(ks[1], cfg, dtype)
    if spec.ff == "dense":
        p["norm2"] = L.norm_init(cfg, cfg.d_model, jnp.float32)
        p["ff"] = L.mlp_init(ks[2], cfg.d_model, cfg.d_ff, dtype)
    elif spec.ff == "moe":
        p["norm2"] = L.norm_init(cfg, cfg.d_model, jnp.float32)
        p["ff"] = MOE.moe_init(ks[2], cfg, dtype)
    return p


def block_cache(cfg: ModelConfig, spec: LayerSpec, batch: int, max_len: int,
                memory_len: int = 0, dtype=jnp.bfloat16,
                layout: str = "seq", page_size: int = 64,
                total_pages: Optional[int] = None,
                cache_dtype: Optional[str] = None) -> Params:
    """Decode-time cache for one block. ``layout`` picks the KV cache
    layout: "seq" (B, S, kv, hd), "head" (B, kv, S, hd) — the flash-decode
    kernel's native layout — or "paged" (page pool + per-row block tables;
    SWA layers keep their head-major ring). ``cache_dtype="int8"``
    quantizes the paged pool per slot (see ``layers.init_kv_cache``)."""
    c: Params = {}
    if spec.mixer in ("attn", "swa"):
        window = cfg.sliding_window if spec.mixer == "swa" else None
        c["attn"] = L.init_kv_cache(cfg, batch, max_len, window, dtype,
                                    layout=layout, page_size=page_size,
                                    total_pages=total_pages,
                                    cache_dtype=cache_dtype)
    elif spec.mixer == "ssm":
        c["ssm"] = SSM.init_ssm_cache(cfg, batch)
    if spec.cross_attn:
        kv, hd = cfg.n_kv_heads, cfg.head_dim
        c["cross_k"] = jnp.zeros((batch, memory_len, kv, hd), dtype=dtype)
        c["cross_v"] = jnp.zeros((batch, memory_len, kv, hd), dtype=dtype)
    return c


@jax.named_scope("attention")
def _attention(p: Params, cfg: ModelConfig, h: jax.Array,
               cache: Optional[Params], pos, positions, *,
               window: Optional[int], decode: bool, prefill: bool,
               causal: bool, offsets, use_kernels: bool):
    """The attention mixer in its three cache modes: (output, the layer's
    new KV cache or None)."""
    if decode:
        return L.attention_decode(p, cfg, h, cache["attn"], pos,
                                  window=window, offsets=offsets,
                                  use_kernels=use_kernels)
    if prefill:
        return L.attention_prefill(p, cfg, h, positions, cache["attn"],
                                   window=window, offsets=offsets,
                                   use_kernels=use_kernels)
    ax = _tp_axis(p["wq"].shape[-1], cfg.n_heads * cfg.head_dim)
    if ax is None:
        return L.attention_full(p, cfg, h, positions, window=window,
                                causal=causal, use_kernels=use_kernels), None
    # Megatron attention: head-split qkv (column-parallel) + head-split wo
    # (row-parallel). The whole sublayer is one partial-sum region:
    # identity-fwd/psum-bwd on everything replicated entering it (the
    # stream AND the per-head-dim qk_norm scales), psum-fwd/identity-bwd on
    # the way out.
    mp = dict(p)
    for nk in ("q_norm", "k_norm"):
        if nk in mp:
            mp[nk] = {"scale": EP.region_in(mp[nk]["scale"], ax)}
    return EP.region_out(
        L.attention_full(mp, cfg, EP.region_in(h, ax), positions,
                         window=window, causal=causal,
                         use_kernels=use_kernels), ax), None


def block_apply(params: Params, cfg: ModelConfig, spec: LayerSpec,
                x: jax.Array, *,
                positions: Optional[jax.Array] = None,
                memory: Optional[jax.Array] = None,
                cache: Optional[Params] = None,
                pos: Optional[jax.Array] = None,
                decode: bool = False,
                causal: bool = True,
                use_kernels: bool = False,
                offsets: Optional[jax.Array] = None,
                ) -> Tuple[jax.Array, Optional[Params], Dict[str, jax.Array]]:
    """Apply one block. Returns (x, new_cache or None, aux).

    Three cache modes: no cache (train / plain forward), ``decode=True``
    (one token against the cache), and PREFILL (``cache`` given with
    ``decode=False``): the full-sequence mixers run once and the resulting
    K/V / SSM state is scattered into the cache in the same pass.
    ``offsets`` (B,) are per-sequence left-pad widths for ragged prompts
    (threaded into the attention validity masks and SSM input masking).
    """
    aux = dict(ZERO_AUX)
    prefill = cache is not None and not decode
    new_cache: Params = {} if cache is not None else None

    if spec.cross_attn:
        h = L.norm_apply(cfg, params["norm_x"], x)
        with jax.named_scope("attention"):
            if decode or prefill:
                y = L.cross_attention_apply(
                    params["cross"], cfg, h, cache["cross_k"],
                    cache["cross_v"])
                new_cache["cross_k"] = cache["cross_k"]
                new_cache["cross_v"] = cache["cross_v"]
            else:
                k, v = L.cross_kv(params["cross"], cfg, memory)
                y = L.cross_attention_apply(params["cross"], cfg, h, k, v)
            x = x + y

    y_mix = None                 # mixer output, residual-add deferred to ff
    if spec.mixer in ("attn", "swa"):
        window = cfg.sliding_window if spec.mixer == "swa" else None
        h = L.norm_apply(cfg, params["norm1"], x)
        y_mix, kvc = _attention(params["mixer"], cfg, h, cache, pos,
                                positions, window=window, decode=decode,
                                prefill=prefill, causal=causal,
                                offsets=offsets, use_kernels=use_kernels)
        if kvc is not None:
            new_cache["attn"] = kvc
    elif spec.mixer == "ssm":
        h = L.norm_apply(cfg, params["norm1"], x)
        with jax.named_scope("ssm"):
            if decode:
                y_mix, sc = SSM.ssm_decode(params["mixer"], cfg, h,
                                           cache["ssm"])
                new_cache["ssm"] = sc
            elif prefill:
                valid = None
                if offsets is not None:
                    valid = jnp.arange(x.shape[1])[None] >= offsets[:, None]
                y_mix, sc = SSM.ssm_prefill(params["mixer"], cfg, h,
                                            valid=valid,
                                            use_kernels=use_kernels)
                old = cache["ssm"]
                new_cache["ssm"] = {
                    "h": sc["h"].astype(old["h"].dtype),
                    "conv": sc["conv"].astype(old["conv"].dtype)}
            else:
                y_mix = SSM.ssm_forward(params["mixer"], cfg, h,
                                        use_kernels=use_kernels)

    if spec.ff == "dense":
        # Fuse the mixer residual add with the ff pre-norm: one pass over
        # the stream instead of add-then-norm (no-op reassociation when
        # use_kernels is off or the norm isn't rmsnorm).
        if y_mix is not None:
            h, x = L.norm_residual_apply(cfg, params["norm2"], x, y_mix,
                                         use_kernels=use_kernels)
        else:
            h = L.norm_apply(cfg, params["norm2"], x)
        with jax.named_scope("mlp"):
            ax = _tp_axis(params["ff"]["w_gate"].shape[-1], cfg.d_ff)
            if ax is not None:
                # Megatron MLP: column-parallel w_gate/w_up, row-parallel
                # w_down.
                x = x + EP.region_out(
                    L.mlp_apply(params["ff"], EP.region_in(h, ax),
                                use_kernels=use_kernels), ax)
            else:
                x = x + L.mlp_apply(params["ff"], h, use_kernels=use_kernels)
    elif spec.ff == "moe":
        if y_mix is not None:
            x = x + y_mix
        h = L.norm_apply(cfg, params["norm2"], x)
        with jax.named_scope("moe"):
            y, moe_aux = MOE.moe_apply(params["ff"], cfg, h)
            aux.update(moe_aux)
            x = x + y
    elif y_mix is not None:
        x = x + y_mix

    return x, new_cache, aux


# ---------------------------------------------------------------------------
# stacks: head (unrolled) + body (scanned) + tail (unrolled)
# ---------------------------------------------------------------------------


def stack_init(rng, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    rh, rb, rt = jax.random.split(rng, 3)
    p: Params = {"head": [], "body": [], "tail": []}
    for i, spec in enumerate(cfg.head_pattern):
        p["head"].append(block_init(jax.random.fold_in(rh, i), cfg, spec, dtype))
    for i, spec in enumerate(cfg.body_pattern):
        slot_rng = jax.random.fold_in(rb, i)
        rngs = jax.random.split(slot_rng, cfg.body_repeats)
        p["body"].append(
            jax.vmap(lambda r: block_init(r, cfg, spec, dtype))(rngs))
    for i, spec in enumerate(cfg.tail_pattern):
        p["tail"].append(block_init(jax.random.fold_in(rt, i), cfg, spec, dtype))
    return p


def stack_cache(cfg: ModelConfig, batch: int, max_len: int,
                memory_len: int = 0, dtype=jnp.bfloat16,
                layout: str = "seq", page_size: int = 64,
                total_pages: Optional[int] = None,
                cache_dtype: Optional[str] = None) -> Params:
    def one(spec):
        return block_cache(cfg, spec, batch, max_len, memory_len, dtype,
                           layout, page_size=page_size,
                           total_pages=total_pages, cache_dtype=cache_dtype)

    def stacked(spec):
        c = one(spec)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.body_repeats,) + a.shape).copy()
            if cfg.body_repeats > 1 else a[None], c)

    return {
        "head": [one(s) for s in cfg.head_pattern],
        "body": [stacked(s) for s in cfg.body_pattern],
        "tail": [one(s) for s in cfg.tail_pattern],
    }


def _sum_aux(acc: Dict, new: Dict) -> Dict:
    return {k: acc[k] + new[k] for k in acc}


def stack_apply(params: Params, cfg: ModelConfig, x: jax.Array, *,
                positions: Optional[jax.Array] = None,
                memory: Optional[jax.Array] = None,
                cache: Optional[Params] = None,
                pos: Optional[jax.Array] = None,
                decode: bool = False,
                causal: bool = True,
                use_kernels: bool = False,
                remat: bool = False,
                seq_parallel: bool = False,
                offsets: Optional[jax.Array] = None,
                ) -> Tuple[jax.Array, Optional[Params], Dict[str, jax.Array]]:
    """Run the full head+body+tail stack.

    ``remat=True`` wraps each block in ``jax.checkpoint`` (full block
    rematerialization) — required for the production train configs, where
    storing per-layer activations for 4k x 256 batches would exceed HBM.
    ``seq_parallel=True`` additionally shards the residual stream over
    (sequence x 'model') between blocks (see ``_sp_hint``).
    ``cache`` with ``decode=False`` is the fused-prefill mode (see
    ``block_apply``); ``offsets`` are the ragged-prompt left-pad widths.
    """
    aux = dict(ZERO_AUX)
    new_cache = {"head": [], "body": [], "tail": []} if cache is not None else None

    def make_block_fn(spec: LayerSpec):
        """Bind the static arguments; optionally wrap in jax.checkpoint."""
        def fn(p, x, c, positions, memory):
            x = _sp_hint(x, seq_parallel)
            out = block_apply(p, cfg, spec, x, cache=c, positions=positions,
                              memory=memory, pos=pos, decode=decode,
                              causal=causal, use_kernels=use_kernels,
                              offsets=offsets)
            return (_sp_hint(out[0], seq_parallel),) + out[1:]
        if remat:
            return jax.checkpoint(
                fn, policy=jax.checkpoint_policies.nothing_saveable)
        return fn

    block_fns = {}

    def apply_block(p, spec, x, c):
        if spec not in block_fns:
            block_fns[spec] = make_block_fn(spec)
        return block_fns[spec](p, x, c, positions, memory)

    for i, spec in enumerate(cfg.head_pattern):
        c = cache["head"][i] if cache is not None else None
        x, nc, a = apply_block(params["head"][i], spec, x, c)
        aux = _sum_aux(aux, a)
        if cache is not None:
            new_cache["head"].append(nc)

    if cfg.body_pattern:
        def body(carry, xs):
            xb = carry
            slot_params, slot_caches = xs
            aux_b = dict(ZERO_AUX)
            ncs = []
            for j, spec in enumerate(cfg.body_pattern):
                c = slot_caches[j] if slot_caches is not None else None
                xb, nc, a = apply_block(slot_params[j], spec, xb, c)
                aux_b = _sum_aux(aux_b, a)
                ncs.append(nc)
            ys = (tuple(ncs) if slot_caches is not None else 0, aux_b)
            return xb, ys

        body_caches = (tuple(cache["body"]) if cache is not None else None)
        xs = (tuple(params["body"]), body_caches) if cache is not None \
            else (tuple(params["body"]), None)
        if cache is not None:
            x, (ncs, aux_b) = jax.lax.scan(body, x, xs)
            new_cache["body"] = list(ncs)
        else:
            # no cache: scan only over params
            def body_nc(carry, slot_params):
                xb, ys = body(carry, (slot_params, None))
                return xb, ys[1]
            x, aux_b = jax.lax.scan(body_nc, x, tuple(params["body"]))
        aux = _sum_aux(aux, jax.tree.map(jnp.sum, aux_b))

    for i, spec in enumerate(cfg.tail_pattern):
        c = cache["tail"][i] if cache is not None else None
        x, nc, a = apply_block(params["tail"][i], spec, x, c)
        aux = _sum_aux(aux, a)
        if cache is not None:
            new_cache["tail"].append(nc)

    return x, new_cache, aux
