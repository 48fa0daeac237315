"""MoE routing invariants (property-based) + EP shard_map equivalence."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs.base import LayerSpec, ModelConfig, MoEConfig
from repro.models import moe as MOE
from repro.models import transformer as T
from repro.configs.registry import get_config
from repro.launch.mesh import make_host_mesh


def _mk_cfg(E=4, k=2, cf=1.25, shared=0):
    return ModelConfig(
        name="t", family="moe", d_model=32, n_heads=2, n_kv_heads=2,
        d_ff=64, vocab_size=64,
        body_pattern=(LayerSpec(mixer="attn", ff="moe"),), body_repeats=1,
        moe=MoEConfig(n_experts=E, top_k=k, d_expert=16,
                      capacity_factor=cf, n_shared_experts=shared,
                      d_shared=16 if shared else 0),
        dtype="float32")


@settings(max_examples=20, deadline=None)
@given(E=st.sampled_from([4, 8]), k=st.integers(1, 3),
       seed=st.integers(0, 100))
def test_property_capacity_never_exceeded(E, k, seed):
    """No expert ever receives more than C tokens (per sequence)."""
    cfg = _mk_cfg(E=E, k=k, cf=1.0)
    m = cfg.moe
    rng = jax.random.PRNGKey(seed)
    params = MOE.moe_init(rng, cfg)
    x = jax.random.normal(jax.random.fold_in(rng, 1), (2, 24, cfg.d_model))
    y, aux = MOE.moe_apply(params, cfg, x)
    assert y.shape == x.shape
    assert not jnp.isnan(y).any()
    assert float(aux["moe_aux"]) >= 0.99   # E*sum f*P >= 1 by Cauchy-Schwarz


def test_dropless_outputs_match_manual():
    """With huge capacity, the MoE output equals the dense per-token sum of
    top-k expert MLPs."""
    cfg = _mk_cfg(E=4, k=2, cf=100.0)
    m = cfg.moe
    rng = jax.random.PRNGKey(0)
    params = MOE.moe_init(rng, cfg)
    x = jax.random.normal(jax.random.fold_in(rng, 1), (1, 8, cfg.d_model))
    y, _ = MOE.moe_apply(params, cfg, x)

    # manual dense computation
    logits = x @ params["router"]
    probs = jax.nn.softmax(logits, -1)
    topw, topi = jax.lax.top_k(probs, 2)
    topw = topw / topw.sum(-1, keepdims=True)
    want = jnp.zeros_like(x)
    for t in range(8):
        acc = jnp.zeros((cfg.d_model,))
        for j in range(2):
            e = int(topi[0, t, j])
            g = jax.nn.silu(x[0, t] @ params["w_gate"][e])
            u = x[0, t] @ params["w_up"][e]
            acc += float(topw[0, t, j]) * ((g * u) @ params["w_down"][e])
        want = want.at[0, t].set(acc)
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-4)


def test_drops_occur_at_low_capacity():
    """With capacity factor << 1 some assignments must drop (output is the
    shared/残 partial sum only for dropped tokens)."""
    cfg = _mk_cfg(E=4, k=1, cf=0.3)
    rng = jax.random.PRNGKey(0)
    params = MOE.moe_init(rng, cfg)
    x = jax.random.normal(jax.random.fold_in(rng, 1), (1, 32, cfg.d_model))
    y_low, _ = MOE.moe_apply(params, cfg, x)
    cfg_hi = _mk_cfg(E=4, k=1, cf=100.0)
    y_hi, _ = MOE.moe_apply(params, cfg_hi, x)
    # some tokens differ (dropped), but not all
    diff = jnp.abs(y_low - y_hi).max(axis=-1)[0]
    assert (diff > 1e-6).any()
    assert (diff < 1e-6).any()


def test_shared_expert_always_on():
    cfg = _mk_cfg(E=4, k=1, cf=0.01, shared=1)   # drop ~everything routed
    rng = jax.random.PRNGKey(0)
    params = MOE.moe_init(rng, cfg)
    x = jax.random.normal(jax.random.fold_in(rng, 1), (1, 16, cfg.d_model))
    y, _ = MOE.moe_apply(params, cfg, x)
    # shared expert output present even for dropped tokens
    from repro.models.layers import mlp_apply
    shared = mlp_apply(params["shared"], x)
    resid = jnp.abs(y - shared).max(axis=-1)[0]
    assert float(resid.min()) < 1e-5


def test_ep_shard_map_equals_fallback():
    """kimi reduced config: EP path under a 1x1 mesh == no-mesh fallback."""
    cfg = dataclasses.replace(get_config("kimi-k2-1t-a32b").reduced(),
                              dtype="float32")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    l0, _ = T.forward(params, cfg, toks)
    with jax.set_mesh(make_host_mesh()):
        l1, _ = jax.jit(lambda p, t: T.forward(p, cfg, t))(params, toks)
    np.testing.assert_allclose(l0, l1, rtol=2e-4, atol=2e-4)


def test_router_weights_normalized():
    cfg = _mk_cfg(E=8, k=3)
    params = MOE.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model))
    topi, topw, aux = MOE._route(params["router"], x, cfg.moe)
    np.testing.assert_allclose(topw.sum(-1), 1.0, rtol=1e-5)
    assert topi.shape == (2, 8, 3)
    # top-k indices are distinct per token
    for b in range(2):
        for t in range(8):
            assert len(set(np.asarray(topi[b, t]).tolist())) == 3
