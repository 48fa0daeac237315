"""shard_map data-parallel training: per-device ghost statistics, gradients
as the only collective.

The single-device-mesh tests run in-process; the multi-device tests run in a
subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the
conftest forbids forcing the device count in-process — smoke tests must keep
seeing the single real device)."""
import dataclasses
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_models import F1_MNIST
from repro.core import LargeBatchConfig, Regime
from repro.launch.mesh import make_data_mesh
from repro.models.cnn import model_fns
from repro.optim import sgd
from repro.train.data_parallel import dp_gbn_forward, make_dp_vision_train_step
from repro.train.trainer import make_vision_train_step

pytestmark = pytest.mark.tier1

REPO = Path(__file__).resolve().parent.parent


def _setup(batch=64, ghost=16):
    cfg = dataclasses.replace(F1_MNIST, input_shape=(8, 8, 1),
                              hidden_sizes=(32,), ghost_batch_size=ghost)
    lb = LargeBatchConfig(batch_size=batch, base_batch_size=batch,
                          ghost_batch_size=ghost)
    regime = Regime(base_lr=0.1, total_steps=10, drop_every=10)
    init_fn, apply_fn = model_fns(cfg)
    params, bn = init_fn(jax.random.PRNGKey(1), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (batch, 8, 8, 1))
    y = jax.random.randint(jax.random.PRNGKey(3), (batch,), 0, 10)
    return cfg, lb, regime, apply_fn, params, bn, x, y


def test_dp_step_single_device_mesh_matches_trainer():
    """On a 1-device mesh the shard_map step must reproduce the plain step
    exactly (same ghosts, one trivial psum)."""
    mesh = make_data_mesh(1)
    cfg, lb, regime, apply_fn, params, bn, x, y = _setup()
    opt = sgd.init(params)
    s1 = jax.jit(make_vision_train_step(apply_fn, cfg, lb, regime))
    sd = jax.jit(make_dp_vision_train_step(apply_fn, cfg, lb, regime, mesh))
    p1, b1, _, m1 = s1(params, bn, opt, x, y, jnp.int32(0),
                       jax.random.PRNGKey(4))
    pd, bd, _, md = sd(params, bn, opt, x, y, jnp.int32(0),
                       jax.random.PRNGKey(4))
    np.testing.assert_allclose(float(m1["loss"]), float(md["loss"]),
                               rtol=1e-6)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(pd)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(b1), jax.tree.leaves(bd)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_dp_gbn_forward_single_device_matches_core():
    mesh = make_data_mesh(1)
    from repro.core.gbn import gbn_apply, gbn_init
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 6)) * 2 + 1
    params, state = gbn_init(6)
    y, mu, var = dp_gbn_forward(x, params["gamma"], params["beta"], mesh,
                                ghost_batch_size=8)
    want, _ = gbn_apply(params, state, x, ghost_batch_size=8)
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    assert mu.shape == (4, 6)


def test_dp_gbn_forward_rejects_ragged_batch():
    mesh = make_data_mesh(1)
    x = jnp.zeros((30, 4))
    with pytest.raises(ValueError):
        dp_gbn_forward(x, jnp.ones((4,)), jnp.zeros((4,)), mesh,
                       ghost_batch_size=16)


MULTIDEV_SCRIPT = textwrap.dedent("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    assert jax.device_count() == 4, jax.device_count()
    from repro.configs.paper_models import F1_MNIST
    from repro.core import LargeBatchConfig, Regime
    from repro.launch.mesh import make_data_mesh
    from repro.models.cnn import model_fns
    from repro.optim import sgd
    from repro.train.data_parallel import (dp_gbn_forward,
                                           make_dp_vision_train_step)
    from repro.train.trainer import make_vision_train_step

    mesh = make_data_mesh()

    # --- per-device ghost statistics: 4 devices x 2 local ghosts of 8 rows.
    # Each stats row must equal the plain mean/var of that device's slice —
    # i.e. the ghost partitioning IS the device partitioning.
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 8)) * 2 + 1
    y, mu, var = dp_gbn_forward(x, jnp.ones((8,)), jnp.zeros((8,)), mesh,
                                ghost_batch_size=8)
    assert mu.shape == (8, 8), mu.shape
    xs = np.asarray(x, np.float32)
    for g in range(8):
        sl = xs[8 * g: 8 * (g + 1)]
        np.testing.assert_allclose(np.asarray(mu[g]), sl.mean(0),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(var[g]), sl.var(0),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(y[8 * g: 8 * (g + 1)]),
            (sl - sl.mean(0)) / np.sqrt(sl.var(0) + 1e-5),
            rtol=1e-4, atol=1e-4)

    # --- kernel path inside shard_map: same stats
    yk, muk, vark = dp_gbn_forward(x, jnp.ones((8,)), jnp.zeros((8,)), mesh,
                                   ghost_batch_size=8, use_kernels=True)
    np.testing.assert_allclose(np.asarray(muk), np.asarray(mu),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(y),
                               rtol=1e-4, atol=1e-4)

    # --- the sharded step takes the same step as the single-device trainer
    # (identical ghost boundaries; grads pmean == global mean grad)
    cfg = dataclasses.replace(F1_MNIST, input_shape=(8, 8, 1),
                              hidden_sizes=(32,), ghost_batch_size=8)
    lb = LargeBatchConfig(batch_size=64, base_batch_size=64,
                          ghost_batch_size=8)
    regime = Regime(base_lr=0.1, total_steps=10, drop_every=10)
    init_fn, apply_fn = model_fns(cfg)
    params, bn = init_fn(jax.random.PRNGKey(1), cfg)
    opt = sgd.init(params)
    xb = jax.random.normal(jax.random.PRNGKey(2), (64, 8, 8, 1))
    yb = jax.random.randint(jax.random.PRNGKey(3), (64,), 0, 10)
    s1 = jax.jit(make_vision_train_step(apply_fn, cfg, lb, regime))
    sd = jax.jit(make_dp_vision_train_step(apply_fn, cfg, lb, regime, mesh))
    p1, _, _, m1 = s1(params, bn, opt, xb, yb, jnp.int32(0),
                      jax.random.PRNGKey(4))
    pd, _, _, md = sd(params, bn, opt, xb, yb, jnp.int32(0),
                      jax.random.PRNGKey(4))
    np.testing.assert_allclose(float(m1["loss"]), float(md["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(pd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
    print("MULTIDEV_OK")
""")


def _run_multidev(script: str) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(REPO / "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env["JAX_PLATFORMS"] = "cpu"   # the child must never reach for a chip
    return subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, env=env,
                          cwd=str(REPO), timeout=600)


def test_dp_multi_device_subprocess():
    """≥2 simulated devices: per-device ghost stats + step equivalence."""
    proc = _run_multidev(MULTIDEV_SCRIPT)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "MULTIDEV_OK" in proc.stdout


RUNNER_MESH_SCRIPT = textwrap.dedent("""
    import dataclasses
    import jax
    assert jax.device_count() == 4, jax.device_count()
    from repro.configs.paper_models import F1_MNIST
    from repro.core import LargeBatchConfig
    from repro.experiments.runner import _mesh_for, run_one
    from repro.experiments.spec import DataSpec, RunSpec

    model = dataclasses.replace(F1_MNIST, input_shape=(8, 8, 1),
                                hidden_sizes=(32,), ghost_batch_size=16)
    spec = RunSpec(name="dp", method="LB", model=model,
                   data=DataSpec(seed=0, n_train=512, n_test=128,
                                 input_shape=(8, 8, 1)),
                   lb=LargeBatchConfig(batch_size=128, base_batch_size=128,
                                       ghost_batch_size=16),
                   base_lr=0.08, total_steps=6, drop_every=3, seed=3,
                   use_mesh=True, track_diffusion=False)
    mesh = _mesh_for(spec)
    assert mesh is not None and mesh.shape["data"] == 4, mesh
    rec = run_one(spec)
    assert 0.0 <= rec["final_acc"] <= 1.0
    # batch 72 does not split 4 ways into whole 16-row ghosts -> no mesh
    bad = dataclasses.replace(
        spec, lb=LargeBatchConfig(batch_size=72, base_batch_size=72,
                                  ghost_batch_size=16))
    assert _mesh_for(bad) is None
    print("RUNNER_MESH_OK")
""")


def test_sweep_runner_fans_over_mesh_subprocess():
    """experiments.runner picks up the ("data",) mesh for use_mesh specs
    whose batch geometry shards evenly, and falls back otherwise."""
    proc = _run_multidev(RUNNER_MESH_SCRIPT)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "RUNNER_MESH_OK" in proc.stdout
