"""Beyond-paper optimization features: exactness guarantees.

- vocab-chunked streaming CE == dense CE (values and gradients)
- grouped (no-repeat) decode attention == repeated-head attention
- int8 momentum last-axis layout roundtrips multi-dim leaves
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models import transformer as T
from repro.models.layers import _sdpa, _sdpa_grouped
from repro.optim.sgd import _dequantize_int8, _quantize_int8


def test_chunked_ce_matches_dense():
    cfg = dataclasses.replace(get_config("qwen3-1.7b").reduced(),
                              dtype="float32")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                                          cfg.vocab_size)}
    l0, _ = T.lm_loss(params, cfg, batch)
    l1, _ = T.lm_loss(params, cfg, batch, ce_chunk=128)
    assert abs(float(l0 - l1)) < 2e-5
    g0 = jax.grad(lambda p: T.lm_loss(p, cfg, batch)[0])(params)
    g1 = jax.grad(lambda p: T.lm_loss(p, cfg, batch, ce_chunk=128)[0])(params)
    err = max(float(jnp.abs(a - b).max())
              for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)))
    assert err < 1e-5


def test_chunked_ce_backward_does_not_save_every_chunk():
    """The point of the streaming CE is its memory: the backward pass
    recomputes each vocab chunk's logits instead of keeping all of them
    (saving them all held 2 x 9.3 GB of f32 logits in a qwen3-1.7b step
    compiled for a TPU v5e)."""
    d, V, chunk, B, S = 64, 8192, 256, 4, 128
    cfg = dataclasses.replace(get_config("qwen3-1.7b").reduced(),
                              dtype="float32", d_model=d, vocab_size=V)
    x = jax.ShapeDtypeStruct((B, S, d), jnp.float32)
    head = jax.ShapeDtypeStruct((cfg.padded_vocab, d), jnp.float32)
    targets = jax.ShapeDtypeStruct((B, S), jnp.int32)
    grad = jax.jit(jax.grad(
        lambda x, h, t: T._chunked_ce(cfg, x, h, t, chunk), argnums=(0, 1)))
    temp = grad.lower(x, head, targets).compile().memory_analysis() \
        .temp_size_in_bytes
    all_chunks = B * S * cfg.padded_vocab * 4         # every chunk's logits
    assert temp < all_chunks / 4, (temp, all_chunks)


def test_chunked_ce_respects_vocab_padding():
    """Padded vocab rows must not receive probability mass."""
    cfg = dataclasses.replace(get_config("seamless-m4t-large-v2").reduced(),
                              dtype="float32", vocab_size=500)
    assert cfg.padded_vocab != cfg.vocab_size
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                          cfg.vocab_size),
             "frames": 0.1 * jax.random.normal(
                 jax.random.PRNGKey(2), (2, 3, cfg.encoder.d_model))}
    l0, _ = T.lm_loss(params, cfg, batch)
    l1, _ = T.lm_loss(params, cfg, batch, ce_chunk=128)
    assert abs(float(l0 - l1)) < 2e-5


def test_grouped_decode_attention_matches_repeated():
    rng = jax.random.PRNGKey(0)
    B, T_, h, kv, hd, S = 2, 1, 8, 2, 32, 40
    q = jax.random.normal(rng, (B, T_, h, hd))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, S, kv, hd))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (B, S, kv, hd))
    mask = (jnp.arange(S) <= 25)[None, None, :]
    out_g = _sdpa_grouped(q, k, v, mask)
    out_r = _sdpa(q, k, v, mask)
    np.testing.assert_allclose(out_g, out_r, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", [(1000,), (7, 300), (3, 5, 512), (2, 256)])
def test_int8_momentum_multidim_roundtrip(shape):
    x = jnp.sin(jnp.arange(np.prod(shape), dtype=jnp.float32)).reshape(shape)
    q = _quantize_int8(x)
    back = _dequantize_int8(q, shape, jnp.float32)
    assert back.shape == x.shape
    # blockwise absmax quantization: error bounded by scale/2 per block
    np.testing.assert_allclose(back, x, atol=float(jnp.abs(x).max()) / 100)
    assert q["q"].shape[:-2] == x.shape[:-1]


def test_mamba_kernel_grads_match_reference():
    from repro.kernels import ops, ref
    B, c, di, ds = 1, 8, 128, 8
    rng = jax.random.PRNGKey(0)
    xc = jax.random.normal(rng, (B, c, di))
    dt = 0.1 * jax.nn.softplus(jax.random.normal(
        jax.random.fold_in(rng, 1), (B, c, di)))
    Bm = jax.random.normal(jax.random.fold_in(rng, 2), (B, c, ds))
    Cm = jax.random.normal(jax.random.fold_in(rng, 3), (B, c, ds))
    A = -jnp.abs(jax.random.normal(jax.random.fold_in(rng, 4), (di, ds)))
    h0 = jnp.zeros((B, di, ds))

    def f(op):
        return lambda *a: op(*a)[0].sum()

    g_k = jax.grad(f(ops.mamba_chunk), argnums=(0, 1, 4))(xc, dt, Bm, Cm, A,
                                                          h0)
    g_r = jax.grad(f(ref.mamba_chunk_ref), argnums=(0, 1, 4))(xc, dt, Bm, Cm,
                                                              A, h0)
    for a, b in zip(g_k, g_r):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
