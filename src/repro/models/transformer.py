"""Top-level model: embeddings + block stack (+ optional encoder / vision
memory) + LM head. Covers all six assigned families:

- dense / moe / ssm / hybrid decoders: ``forward`` (train / prefill) and
  ``decode_step`` (one token against caches).
- encdec (audio): ``encode`` runs the transformer encoder over the stubbed
  frame embeddings; the decoder cross-attends the encoded memory.
- vlm: the decoder cross-attends the stubbed projected patch embeddings.

``use_kernels=True`` on the forward/loss entry points routes the mixers
through the differentiable Pallas kernels (flash attention with its
dedicated backward pair, the Mamba chunk scan likewise) — the LM train
step's hot path under the paper's "train longer" regime.

Besides the blocks' sublayer scopes (:mod:`.blocks`), ``embed`` names the
embedding lookup, ``ce`` the loss with its LM-head matmul, and ``head`` the
LM head where the logits are the output (forward, prefill, decode).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import blocks as B
from repro.models import layers as L
from repro.sharding import hints

Params = Dict[str, Any]


def _compute_dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


@jax.named_scope("embed")
def _embed(params: Params, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    return params["embed"][tokens].astype(_compute_dtype(cfg))


def _lm_head(params: Params, cfg: ModelConfig) -> jax.Array:
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    return head.astype(_compute_dtype(cfg))


def encoder_config(cfg: ModelConfig) -> ModelConfig:
    """Internal ModelConfig for the (non-causal) encoder stack."""
    e = cfg.encoder
    return dataclasses.replace(
        cfg,
        name=cfg.name + "-encoder",
        d_model=e.d_model,
        n_heads=e.n_heads,
        n_kv_heads=e.n_kv_heads,
        head_dim=e.d_model // e.n_heads,
        d_ff=e.d_ff,
        head_pattern=(),
        body_pattern=(LayerSpec(mixer="attn", ff="dense"),),
        body_repeats=e.n_layers,
        tail_pattern=(),
        causal=False,
        moe=None, ssm=None, encoder=None, vision=None,
    )


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(rng, cfg: ModelConfig) -> Params:
    dtype = _compute_dtype(cfg)
    r_embed, r_stack, r_head, r_enc = jax.random.split(rng, 4)
    Vp, d = cfg.padded_vocab, cfg.d_model
    p: Params = {
        "embed": L.dense_init(r_embed, (Vp, d), scale=0.02, dtype=dtype),
        "stack": B.stack_init(r_stack, cfg, dtype),
        "final_norm": L.norm_init(cfg, d, jnp.float32),
    }
    if not cfg.tie_embeddings:
        p["head"] = L.dense_init(r_head, (Vp, d), scale=0.02, dtype=dtype)
    if cfg.encoder is not None:
        ecfg = encoder_config(cfg)
        p["encoder"] = {
            "stack": B.stack_init(r_enc, ecfg, dtype),
            "final_norm": L.norm_init(ecfg, ecfg.d_model, jnp.float32),
        }
    return p


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def encode(params: Params, cfg: ModelConfig, frames: jax.Array,
           use_kernels: bool = False, remat: bool = False,
           seq_parallel: bool = False) -> jax.Array:
    """Encoder over stub frame embeddings (B, F, d_model)."""
    ecfg = encoder_config(cfg)
    Bsz, F, _ = frames.shape
    positions = jnp.broadcast_to(jnp.arange(F)[None], (Bsz, F))
    x, _, _ = B.stack_apply(params["encoder"]["stack"], ecfg, frames,
                            positions=positions, causal=False,
                            use_kernels=use_kernels, remat=remat,
                            seq_parallel=seq_parallel)
    return L.norm_apply(ecfg, params["encoder"]["final_norm"], x)


def get_memory(params: Params, cfg: ModelConfig,
               batch: Dict[str, jax.Array],
               use_kernels: bool = False, remat: bool = False,
               seq_parallel: bool = False) -> Optional[jax.Array]:
    """Resolve the cross-attention memory for this family, if any."""
    if cfg.encoder is not None:
        return encode(params, cfg, batch["frames"], use_kernels,
                      remat=remat, seq_parallel=seq_parallel)
    if cfg.vision is not None:
        return batch["image_embeds"]
    return None


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array, *,
            memory: Optional[jax.Array] = None,
            use_kernels: bool = False,
            remat: bool = False,
            seq_parallel: bool = False) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """tokens: (B, S) int32 -> (logits (B, S, V), aux losses)."""
    x, aux = hidden_states(params, cfg, tokens, memory=memory,
                           use_kernels=use_kernels, remat=remat,
                           seq_parallel=seq_parallel)
    with jax.named_scope("head"):
        logits = _logits(params, cfg, x)
    return logits, aux


def _logits(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    return hints.hint(x @ _lm_head(params, cfg).T, "dp", None, "model")


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               memory_len: int = 0, dtype=jnp.bfloat16,
               layout: str = "seq", page_size: int = 64,
               total_pages: Optional[int] = None,
               cache_dtype: Optional[str] = None) -> Params:
    """``layout="head"`` builds the flash-decode kernel's native head-major
    KV caches (serving ``use_kernels=True``); "seq" is the classic
    (B, S, kv, hd) layout the grouped-einsum decode and sharding rules
    expect; "paged" gives full-attention layers a physical page pool +
    per-row block tables (``page_size`` slots per page, ``total_pages``
    including the reserved trash page 0) for the continuous-batching
    engine — SWA ring and SSM/cross caches are unchanged by it.
    ``cache_dtype="int8"`` stores the paged pool as per-slot symmetric
    int8 codes plus f32 scale planes (``ks``/``vs``), halving the kp/vp
    payload so the same pool memory holds twice the slots."""
    return B.stack_cache(cfg, batch, max_len, memory_len, dtype, layout,
                         page_size=page_size, total_pages=total_pages,
                         cache_dtype=cache_dtype)


def memory_len(cfg: ModelConfig, seq_len: int) -> int:
    if cfg.encoder is not None:
        return seq_len // cfg.encoder.frame_ratio
    if cfg.vision is not None:
        return cfg.vision.n_image_tokens
    return 0


def build_cross_cache(params: Params, cfg: ModelConfig, memory: jax.Array,
                      cache: Params) -> Params:
    """Fill the per-layer projected cross K/V into a fresh cache."""
    def fill(section, blk_params, spec, stacked: bool):
        if not spec.cross_attn:
            return section
        cross = blk_params["cross"]
        if stacked:
            k, v = jax.vmap(lambda cp: L.cross_kv(cp, cfg, memory))(cross)
        else:
            k, v = L.cross_kv(cross, cfg, memory)
        section = dict(section)
        section["cross_k"] = k.astype(section["cross_k"].dtype)
        section["cross_v"] = v.astype(section["cross_v"].dtype)
        return section

    new = {"head": [], "body": [], "tail": []}
    for i, spec in enumerate(cfg.head_pattern):
        new["head"].append(
            fill(cache["head"][i], params["stack"]["head"][i], spec, False))
    for j, spec in enumerate(cfg.body_pattern):
        new["body"].append(
            fill(cache["body"][j], params["stack"]["body"][j], spec, True))
    for i, spec in enumerate(cfg.tail_pattern):
        new["tail"].append(
            fill(cache["tail"][i], params["stack"]["tail"][i], spec, False))
    return new


def decode_step(params: Params, cfg: ModelConfig, tokens: jax.Array,
                cache: Params, pos: jax.Array, *,
                use_kernels: bool = False,
                offsets: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Params]:
    """tokens: (B, 1) int32; pos: scalar int32 (lockstep batch) or per-row
    (B,) int32 (continuous batching) -> (logits (B,1,V), new cache).

    ``use_kernels=True`` routes cache attention through the Pallas
    flash-decode kernel. ``offsets`` (B,) are per-sequence left-pad widths
    for ragged (left-padded) prompts: RoPE positions shift to
    ``pos - offsets`` and padded cache slots are masked out of every
    attention."""
    x = _embed(params, cfg, tokens)
    x, new_cache, _ = B.stack_apply(params["stack"], cfg, x, cache=cache,
                                    pos=pos, decode=True,
                                    use_kernels=use_kernels, offsets=offsets)
    x = L.norm_apply(cfg, params["final_norm"], x)
    with jax.named_scope("head"):
        logits = x @ _lm_head(params, cfg).T
    return logits, new_cache


def prefill_forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
                    cache: Params, *,
                    use_kernels: bool = False,
                    offsets: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, Params]:
    """Fused prefill: ONE full-sequence forward that scatters every layer's
    K/V (and SSM state) into the decode cache and returns only the
    last-position logits.

    tokens: (B, P) int32 -> (logits (B, 1, V), filled cache). Cross-attention
    caches must already be filled (``build_cross_cache``). With ``offsets``
    (left-padded ragged prompts) the per-row RoPE positions start at each
    sequence's first real token and padded positions are masked out of the
    attention and SSM state — so the filled cache matches what each
    sequence would produce unpadded. The last column is each sequence's
    final prompt token (left padding), so one logits row serves every row.
    """
    Bsz, P = tokens.shape
    x = _embed(params, cfg, tokens)
    base = jnp.broadcast_to(jnp.arange(P)[None], (Bsz, P))
    positions = base if offsets is None else base - offsets[:, None]
    x, new_cache, _ = B.stack_apply(params["stack"], cfg, x, cache=cache,
                                    positions=positions, decode=False,
                                    causal=cfg.causal,
                                    use_kernels=use_kernels, offsets=offsets)
    x = L.norm_apply(cfg, params["final_norm"], x[:, -1:])
    with jax.named_scope("head"):
        logits = x @ _lm_head(params, cfg).T
    return logits, new_cache


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------


def hidden_states(params: Params, cfg: ModelConfig, tokens: jax.Array, *,
                  memory: Optional[jax.Array] = None,
                  use_kernels: bool = False, remat: bool = False,
                  seq_parallel: bool = False):
    """Run the stack up to (but excluding) the LM head."""
    Bsz, S = tokens.shape
    x = _embed(params, cfg, tokens)
    positions = jnp.broadcast_to(jnp.arange(S)[None], (Bsz, S))
    x, _, aux = B.stack_apply(params["stack"], cfg, x, positions=positions,
                              memory=memory, causal=cfg.causal,
                              use_kernels=use_kernels, remat=remat,
                              seq_parallel=seq_parallel)
    return L.norm_apply(cfg, params["final_norm"], x), aux


def _dense_ce(cfg: ModelConfig, logits: jax.Array,
              targets: jax.Array) -> jax.Array:
    logits = logits.astype(jnp.float32)
    if cfg.padded_vocab != cfg.vocab_size:
        pad = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        logits = jnp.where(pad[None, None, :], -1e30, logits)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return (logz - gold).mean()


def _chunked_ce(cfg: ModelConfig, x: jax.Array, head: jax.Array,
                targets: jax.Array, chunk: int) -> jax.Array:
    """Vocab-chunked streaming softmax CE (beyond-paper memory optimization,
    EXPERIMENTS.md #Perf): the (B, S, V) f32 logits tensor is never
    materialised — logits are computed one V-chunk at a time inside a scan
    whose body is checkpointed, so the backward pass recomputes each chunk
    instead of saving all of them."""
    Vp = cfg.padded_vocab
    assert Vp % chunk == 0, (Vp, chunk)
    n = Vp // chunk
    dt = x.dtype
    B_, S_ = targets.shape
    head_c = head.reshape(n, chunk, x.shape[-1])

    def body(carry, inp):
        m_run, s_run, gold = carry
        hc, ci = inp
        lg = (x @ hc.astype(dt).T).astype(jnp.float32)     # (B, S, chunk)
        base = ci * chunk
        vid = base + jnp.arange(chunk)
        if cfg.padded_vocab != cfg.vocab_size:
            lg = jnp.where((vid >= cfg.vocab_size)[None, None, :], -1e30, lg)
        m_new = jnp.maximum(m_run, lg.max(-1))
        s_run = s_run * jnp.exp(m_run - m_new) \
            + jnp.exp(lg - m_new[..., None]).sum(-1)
        in_chunk = (targets >= base) & (targets < base + chunk)
        idx = jnp.clip(targets - base, 0, chunk - 1)
        g = jnp.take_along_axis(lg, idx[..., None], axis=-1)[..., 0]
        gold = jnp.where(in_chunk, g, gold)
        return (m_new, s_run, gold), None

    init = (jnp.full((B_, S_), -1e30, jnp.float32),
            jnp.zeros((B_, S_), jnp.float32),
            jnp.zeros((B_, S_), jnp.float32))
    (m_run, s_run, gold), _ = jax.lax.scan(
        jax.checkpoint(body), init, (head_c, jnp.arange(n)))
    logz = m_run + jnp.log(jnp.maximum(s_run, 1e-30))
    return (logz - gold).mean()


def lm_loss(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array], *,
            use_kernels: bool = False,
            remat: bool = False,
            seq_parallel: bool = False,
            ce_chunk: int = 0
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token cross-entropy + MoE auxiliary losses.

    ``ce_chunk > 0`` switches to the vocab-chunked streaming CE (#Perf)."""
    tokens = batch["tokens"]
    memory = get_memory(params, cfg, batch, use_kernels,
                        remat=remat, seq_parallel=seq_parallel)
    targets = tokens[:, 1:]
    x, aux = hidden_states(params, cfg, tokens, memory=memory,
                           use_kernels=use_kernels, remat=remat,
                           seq_parallel=seq_parallel)
    with jax.named_scope("ce"):
        if ce_chunk and cfg.padded_vocab % ce_chunk == 0:
            head = params["embed"] if cfg.tie_embeddings else params["head"]
            ce = _chunked_ce(cfg, x[:, :-1], head, targets, ce_chunk)
        else:
            ce = _dense_ce(cfg, _logits(params, cfg, x)[:, :-1], targets)
    m = cfg.moe
    total = ce
    if m is not None:
        total = (total + m.router_aux_weight * aux["moe_aux"]
                 + m.router_z_weight * aux["moe_z"])
    metrics = {"ce": ce, **aux}
    return total, metrics
