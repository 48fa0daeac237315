"""Sharding rules + hints: spec shapes are consistent, divisibility fallback
works, a full train step runs under a host mesh (1x1) with the same code
path the production mesh uses."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.registry import get_config
from repro.core import LargeBatchConfig, Regime
from repro.launch.mesh import dp_axes, fsdp_axes, make_host_mesh
from repro.models import transformer as T
from repro.optim import sgd
from repro.sharding import rules
from repro.sharding.hints import current_mesh, hint
from repro.train.trainer import make_lm_train_step


def test_param_specs_cover_tree():
    cfg = dataclasses.replace(get_config("qwen3-1.7b").reduced(),
                              dtype="float32")
    params = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    mesh = make_host_mesh()
    specs = rules.param_specs(params, mesh, cfg)
    flat_p = jax.tree.leaves(params)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P))
    assert len(flat_p) == len(flat_s)
    for p, s in zip(flat_p, flat_s):
        assert len(s) <= len(p.shape)


def test_divisibility_fallback():
    """Dims not divisible by the mesh axis size are replicated."""
    class FakeLeaf:
        def __init__(self, shape):
            self.shape = shape

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    # 60 experts % 16 != 0 on a real 16-way mesh would fall back; on the 1x1
    # host mesh everything divides — check the rule helper directly instead.
    from repro.sharding.rules import _fits
    class M:
        shape = {"data": 16, "model": 16}
    assert _fits(64, M, "model")
    assert not _fits(60, M, "model")
    assert _fits(60, M, None)
    assert not _fits(60, M, ("data", "model"))


def test_hint_noop_without_mesh():
    x = jnp.ones((4, 4))
    y = hint(x, "dp", "model")
    np.testing.assert_array_equal(x, y)
    assert current_mesh() is None


def test_hint_rank_mismatch_raises():
    with jax.set_mesh(make_host_mesh()):
        with pytest.raises(ValueError):
            hint(jnp.ones((2, 2)), "dp")


def test_train_step_under_host_mesh():
    """The exact production code path (hints + EP + remat + SP) on a 1x1
    mesh: one jitted train step with sharded params."""
    cfg = dataclasses.replace(get_config("jamba-v0.1-52b").reduced(),
                              dtype="float32")
    mesh = make_host_mesh()
    rng = jax.random.PRNGKey(0)
    params = T.init_params(rng, cfg)
    opt = sgd.init(params)
    pshard = rules.param_shardings(params, mesh, cfg)
    params = jax.device_put(params, pshard)
    lb = LargeBatchConfig(batch_size=2, base_batch_size=2, grad_clip=1.0)
    regime = Regime(base_lr=0.01, total_steps=5, drop_every=5)
    step = make_lm_train_step(cfg, lb, regime, remat=True, seq_parallel=True)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                          cfg.vocab_size)}
    with jax.set_mesh(mesh):
        p2, o2, m = jax.jit(step)(params, opt, batch, jnp.int32(0),
                                  jax.random.PRNGKey(2))
    assert not jnp.isnan(m["loss"])


def test_cache_specs_structure():
    cfg = dataclasses.replace(get_config("gemma3-27b").reduced(),
                              dtype="float32")
    cache = jax.eval_shape(lambda: T.init_cache(cfg, 4, 64))
    mesh = make_host_mesh()
    specs = rules.cache_specs(cache, mesh, 4)
    ncache = len(jax.tree.leaves(cache))
    nspecs = len(jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P)))
    assert ncache == nspecs


def test_mesh_axis_helpers():
    single = make_host_mesh()
    assert dp_axes(single) == ("data",)
    assert fsdp_axes(single) == ("data",)
