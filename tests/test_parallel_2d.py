"""Unified 2-D parallelism layer (train/parallel.py): the LM/MoE train step
sharded data x model matches the single-device step, geometry gating, and
the experiments runner's topology ladder.

In-process tests use the degenerate 1x1 host mesh or a shape-only mesh stub
(tier0 quick gate); the real multi-device tests run in a subprocess with 4
simulated devices as a (2 data, 2 model) mesh (the conftest forbids forcing
the device count in-process)."""
import dataclasses
import os
import subprocess
import sys
import textwrap
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core import LargeBatchConfig, Regime
from repro.launch.mesh import dp_axes, dp_size, make_host_mesh
from repro.models import transformer as T
from repro.optim import sgd
from repro.train.parallel import mesh_compatible, mesh_param_specs
from repro.train.trainer import make_lm_train_step

pytestmark = pytest.mark.tier1

REPO = Path(__file__).resolve().parent.parent


def _mesh_stub(**axes):
    """Shape-only mesh: enough for spec/geometry functions (no devices)."""
    return SimpleNamespace(shape=dict(axes), axis_names=tuple(axes))


def _reduced(arch: str):
    return dataclasses.replace(get_config(arch).reduced(), dtype="float32",
                               vocab_size=128)


# ---------------------------------------------------------------------------
# tier0: degenerate host mesh + geometry gating (no simulated devices)
# ---------------------------------------------------------------------------


@pytest.mark.tier0
def test_host_mesh_lm_step_matches_plain():
    """On the degenerate (1, 1) host mesh the unified step must reproduce
    the plain LM step exactly (size-1 psums, grad-clip norm included)."""
    cfg = _reduced("kimi-k2-1t-a32b")
    lb = LargeBatchConfig(batch_size=4, base_batch_size=4, grad_clip=1.0)
    regime = Regime(base_lr=0.02, total_steps=10, drop_every=5)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    opt = sgd.init(params)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 16),
                                          0, cfg.vocab_size)}
    s1 = jax.jit(make_lm_train_step(cfg, lb, regime))
    s2 = jax.jit(make_lm_train_step(cfg, lb, regime, mesh=make_host_mesh(),
                                    params=params))
    p1, _, m1 = s1(params, opt, batch, jnp.int32(0), jax.random.PRNGKey(2))
    p2, _, m2 = s2(params, opt, batch, jnp.int32(0), jax.random.PRNGKey(2))
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-6)
    np.testing.assert_allclose(float(m1["grad_norm"]), float(m2["grad_norm"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


@pytest.mark.tier0
def test_data_mesh_lm_step_matches_plain():
    """The unified LM step on a mesh WITHOUT a 'model' axis (the legacy 1-D
    ("data",) mesh _mesh_for's ladder can fall back to): everything
    replicates except the batch, and the pjit spec rules — which assume a
    'model' axis — must not be consulted."""
    from repro.launch.mesh import make_data_mesh
    cfg = _reduced("qwen3-1.7b")
    lb = LargeBatchConfig(batch_size=4, base_batch_size=4, grad_clip=1.0)
    regime = Regime(base_lr=0.02, total_steps=10, drop_every=5)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    opt = sgd.init(params)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 16),
                                          0, cfg.vocab_size)}
    s1 = jax.jit(make_lm_train_step(cfg, lb, regime))
    s2 = jax.jit(make_lm_train_step(cfg, lb, regime, mesh=make_data_mesh(1),
                                    params=params))
    p1, _, m1 = s1(params, opt, batch, jnp.int32(0), jax.random.PRNGKey(2))
    p2, _, m2 = s2(params, opt, batch, jnp.int32(0), jax.random.PRNGKey(2))
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-6)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


@pytest.mark.tier0
def test_run_id_topology_canonicalization():
    """use_mesh=True and use_mesh="data" are the same request and must hash
    to the same run_id (True is the legacy encoding recorded in existing
    sweep stores); "2d" is distinct."""
    from repro.experiments.registry import get_sweep
    base = get_sweep("lm-smoke", steps=2).expand()[0]
    s_true = dataclasses.replace(base, use_mesh=True)
    s_data = dataclasses.replace(base, use_mesh="data")
    s_2d = dataclasses.replace(base, use_mesh="2d")
    s_off = dataclasses.replace(base, use_mesh="")
    assert s_true.run_id == s_data.run_id
    assert s_true.to_json()["use_mesh"] is True
    assert s_2d.run_id != s_true.run_id
    assert s_off.run_id == base.run_id


@pytest.mark.tier0
def test_mesh_lm_step_requires_params():
    cfg = _reduced("qwen3-1.7b")
    lb = LargeBatchConfig(batch_size=4, base_batch_size=4)
    with pytest.raises(ValueError):
        make_lm_train_step(cfg, lb, Regime(base_lr=0.1, total_steps=1,
                                           drop_every=1),
                           mesh=make_host_mesh())


@pytest.mark.tier0
def test_mesh_compatible_2d_geometry():
    """batch % dp size, whole ghosts per dp shard, experts % model size."""
    mesh = _mesh_stub(data=2, model=2)
    lb = LargeBatchConfig(batch_size=64, base_batch_size=64,
                          ghost_batch_size=16)
    assert mesh_compatible(lb, mesh)                       # 32 per dp shard
    assert not mesh_compatible(lb, mesh, batch_size=6)     # 6 % 2 != 0
    # 36/2 = 18 rows per dp shard: not whole 16-row ghosts
    assert not mesh_compatible(lb, mesh, batch_size=36)
    nogbn = dataclasses.replace(lb, use_gbn=False)
    assert mesh_compatible(nogbn, mesh, batch_size=36)
    # MoE expert geometry over the model axis
    kimi = _reduced("kimi-k2-1t-a32b")                     # 4 experts
    assert mesh_compatible(nogbn, mesh, batch_size=8, cfg=kimi)
    odd = dataclasses.replace(
        kimi, moe=dataclasses.replace(kimi.moe, n_experts=3, d_expert=129))
    assert not mesh_compatible(nogbn, mesh, batch_size=8, cfg=odd)
    # ffn fallback: experts don't divide but each expert's hidden does
    ffn = dataclasses.replace(
        kimi, moe=dataclasses.replace(kimi.moe, n_experts=3, d_expert=128))
    assert mesh_compatible(nogbn, mesh, batch_size=8, cfg=ffn)
    # dense cfg: the model axis just replicates — always compatible
    assert mesh_compatible(nogbn, mesh, batch_size=8,
                           cfg=_reduced("qwen3-1.7b"))
    # pod axis folds into the dp ways
    pod = _mesh_stub(pod=2, data=2, model=2)
    assert dp_size(pod) == 4 and dp_axes(pod) == ("pod", "data")
    assert mesh_compatible(nogbn, pod, batch_size=8)
    assert not mesh_compatible(nogbn, pod, batch_size=6)


@pytest.mark.tier0
def test_mesh_param_specs_expert_only():
    """Expert tensors keep 'model' (expert axis when it divides, hidden dim
    otherwise); attention/dense/shared-expert weights are replicated even
    though the pjit rules Megatron-shard them."""
    mesh = _mesh_stub(data=2, model=2)
    specs = mesh_param_specs(T.init_params(jax.random.PRNGKey(0),
                                           _reduced("kimi-k2-1t-a32b")),
                             mesh)
    body_ff = specs["stack"]["body"][0]["ff"]
    assert tuple(body_ff["w_gate"]) == (None, "model", None, None)
    assert tuple(body_ff["w_down"]) == (None, "model", None, None)
    assert all(e is None for e in body_ff["router"])
    for leaf in jax.tree.leaves(specs["stack"]["body"][0]["mixer"]):
        assert all(e is None for e in leaf), leaf
    for leaf in jax.tree.leaves(body_ff["shared"]):
        assert all(e is None for e in leaf), leaf
    assert all(e is None for e in specs["embed"])


# ---------------------------------------------------------------------------
# multi-device subprocess: (2 data, 2 model)
# ---------------------------------------------------------------------------


def _run_multidev(script: str) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(REPO / "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env["JAX_PLATFORMS"] = "cpu"   # the child must never reach for a chip
    return subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, env=env,
                          cwd=str(REPO), timeout=900)


LM_2D_SCRIPT = textwrap.dedent("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    assert jax.device_count() == 4, jax.device_count()
    from repro.configs.base import LayerSpec, ModelConfig, MoEConfig
    from repro.configs.registry import get_config
    from repro.core import LargeBatchConfig, Regime
    from repro.launch.mesh import make_2d_mesh
    from repro.models import transformer as T
    from repro.optim import sgd
    from repro.train.trainer import make_lm_train_step

    mesh = make_2d_mesh()
    assert dict(mesh.shape) == {"data": 2, "model": 2}, mesh

    lb = LargeBatchConfig(batch_size=8, base_batch_size=8, grad_clip=1.0)
    regime = Regime(base_lr=0.02, total_steps=10, drop_every=5)

    def run(cfg, steps=3, use_kernels=False):
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        s1 = jax.jit(make_lm_train_step(cfg, lb, regime,
                                        use_kernels=use_kernels))
        s2 = jax.jit(make_lm_train_step(cfg, lb, regime, mesh=mesh,
                                        params=params,
                                        use_kernels=use_kernels))
        p1 = p2 = params
        o1 = o2 = sgd.init(params)
        for k in range(steps):
            toks = jax.random.randint(
                jax.random.fold_in(jax.random.PRNGKey(1), k), (8, 16),
                0, cfg.vocab_size)
            b = {"tokens": toks}
            p1, o1, m1 = s1(p1, o1, b, jnp.int32(k),
                            jax.random.PRNGKey(2 + k))
            p2, o2, m2 = s2(p2, o2, b, jnp.int32(k),
                            jax.random.PRNGKey(2 + k))
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=1e-5)
        np.testing.assert_allclose(float(m1["grad_norm"]),
                                   float(m2["grad_norm"]), rtol=1e-4)
        for a, c in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       rtol=2e-5, atol=1e-6)
        return p2

    def reduced(arch):
        return dataclasses.replace(get_config(arch).reduced(),
                                   dtype="float32", vocab_size=128)

    # dense: model axis replicates, dp axes shard the batch
    run(reduced("qwen3-1.7b"), steps=2)

    # kimi (4 experts % 2 == 0): expert weights sharded over 'model'
    kimi = reduced("kimi-k2-1t-a32b")
    p2 = run(kimi)
    spec = p2["stack"]["body"][0]["ff"]["w_gate"].sharding.spec
    assert tuple(spec)[:2] == (None, "model"), spec

    # qwen2-moe through the Pallas kernels (flash attention fwd+bwd
    # inside the shard_map region), 1 step for time
    run(reduced("qwen2-moe-a2.7b"), steps=1, use_kernels=True)

    # 3 experts don't divide model=2 -> ffn sharding of d_expert
    ffn = ModelConfig(
        name="ffn3", family="moe", d_model=64, n_heads=2, n_kv_heads=2,
        d_ff=128, vocab_size=128,
        body_pattern=(LayerSpec(mixer="attn", ff="moe"),), body_repeats=2,
        moe=MoEConfig(n_experts=3, top_k=2, d_expert=64,
                      capacity_factor=1.5),
        dtype="float32")
    p2 = run(ffn)
    spec = p2["stack"]["body"][0]["ff"]["w_gate"].sharding.spec
    assert tuple(spec) == (None, None, None, "model"), spec
    print("LM_2D_OK")
""")


def test_lm_2d_matches_single_device_subprocess():
    """(2 data, 2 model): sharded LM step == unsharded step after multiple
    steps — dense, expert-sharded MoE (kimi), ffn-sharded MoE, and the
    Pallas-kernel path; expert weights actually land sharded over 'model'
    and gradients pmean over dp only (equality would break otherwise)."""
    proc = _run_multidev(LM_2D_SCRIPT)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "LM_2D_OK" in proc.stdout


VISION_2D_SCRIPT = textwrap.dedent("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    assert jax.device_count() == 4, jax.device_count()
    from repro.configs.paper_models import F1_MNIST
    from repro.core import LargeBatchConfig, Regime
    from repro.launch.mesh import make_2d_mesh
    from repro.models.cnn import model_fns
    from repro.optim import sgd
    from repro.train.data_parallel import make_dp_vision_train_step
    from repro.train.trainer import make_vision_train_step

    mesh = make_2d_mesh()
    # 2 dp shards x 2 model shards: 32 rows per dp shard, 4 ghosts of 8
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
    p1, b1, _, m1 = s1(params, bn, opt, xb, yb, jnp.int32(0),
                       jax.random.PRNGKey(4))
    pd, bd, _, md = sd(params, bn, opt, xb, yb, jnp.int32(0),
                       jax.random.PRNGKey(4))
    np.testing.assert_allclose(float(m1["loss"]), float(md["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(pd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree.leaves(b1), jax.tree.leaves(bd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
    print("VISION_2D_OK")
""")


def test_vision_2d_matches_single_device_subprocess():
    """The generalized vision DP step on a (2, 2) mesh: batch shards over
    the 2 dp ways (the model axis replicates), ghost stats stay local, and
    the step matches the single-device trainer."""
    proc = _run_multidev(VISION_2D_SCRIPT)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "VISION_2D_OK" in proc.stdout


RUNNER_2D_SCRIPT = textwrap.dedent("""
    import dataclasses
    import jax
    assert jax.device_count() == 4, jax.device_count()
    from repro.experiments.registry import get_sweep
    from repro.experiments.runner import _mesh_for, run_one

    # lm-smoke over the 2-D mesh on an MoE arch: geometry fits (batch 8
    # over 2 dp ways, 4 experts over 2 model ways)
    sweep = get_sweep("lm-smoke", steps=4, arch="kimi-k2-1t-a32b",
                      use_mesh="2d")
    spec = sweep.expand()[0]
    mesh = _mesh_for(spec)
    assert mesh is not None and dict(mesh.shape) == {"data": 2, "model": 2}
    # kernels-off for the end-to-end run: interpret-mode Pallas backward
    # dominates the wall clock and the kernel path's 2-D equivalence is
    # covered by test_lm_2d_matches_single_device_subprocess
    rec = run_one(dataclasses.replace(spec, use_kernels=False))
    assert rec["final_ce"] > 0
    # geometry that fits no mesh (batch 6: 6 % 2 dp ways is fine, but a
    # batch of 7 splits neither 2-D nor 1-D) -> clean fallback to None
    bad = dataclasses.replace(
        spec, lb=dataclasses.replace(spec.lb, batch_size=7))
    assert _mesh_for(bad) is None
    # 2-D incompatible but 1-D compatible (odd experts, odd hidden):
    # ladder degrades to the ("data",) mesh
    from repro.configs.registry import get_config
    from repro.experiments.runner import _lm_config
    from repro.train.parallel import mesh_compatible
    from repro.launch.mesh import make_data_mesh
    cfg = _lm_config(spec)
    odd = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, n_experts=3, d_expert=129))
    assert not mesh_compatible(spec.lb, _mesh_for(spec), cfg=odd)
    assert mesh_compatible(spec.lb, make_data_mesh(), cfg=odd)
    # use_mesh=True keeps meaning the 1-D data mesh
    legacy = dataclasses.replace(spec, use_mesh=True)
    m1d = _mesh_for(legacy)
    assert m1d is not None and tuple(m1d.axis_names) == ("data",)
    # a dense arch has nothing to shard over 'model': a "2d" request takes
    # the full-width data mesh instead of wasting half the devices on
    # replication
    dense = dataclasses.replace(spec, lm_arch="qwen3-1.7b")
    md = _mesh_for(dense)
    assert md is not None and tuple(md.axis_names) == ("data",), md
    assert md.shape["data"] == 4
    print("RUNNER_2D_OK")
""")


def test_runner_fans_lm_over_2d_mesh_subprocess():
    """experiments.runner: use_mesh="2d" fans an lm-smoke MoE run over the
    (2 data, 2 model) mesh when the geometry allows, degrades down the
    topology ladder when it doesn't, and use_mesh=True still selects the
    1-D data mesh."""
    proc = _run_multidev(RUNNER_2D_SCRIPT)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "RUNNER_2D_OK" in proc.stdout


# ---------------------------------------------------------------------------
# tier0: Megatron-TP + FSDP spec derivation and state-memory math
# ---------------------------------------------------------------------------


@pytest.mark.tier0
def test_mesh_param_specs_tp_and_fsdp():
    """tp=True head-splits attention projections and column/row-splits the
    dense MLP over 'model'; fsdp=True shards every remaining large tensor
    over the dp axes; embed/head stay model-replicated (vocab parallelism
    is not built)."""
    mesh = _mesh_stub(data=2, model=2)
    cfg = _reduced("qwen3-1.7b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    specs = mesh_param_specs(params, mesh, cfg=cfg, tp=True, fsdp=True)
    mixer = specs["stack"]["body"][0]["mixer"]
    # column-parallel qkv: head dim over 'model', fsdp over 'data'
    assert tuple(mixer["wq"]) == (None, "data", "model")
    assert tuple(mixer["wk"]) == (None, "data", "model")
    # row-parallel o: input (head) dim over 'model'
    assert tuple(mixer["wo"]) == (None, "model", "data")
    ff = specs["stack"]["body"][0]["ff"]
    assert tuple(ff["w_gate"]) == (None, "data", "model")
    assert tuple(ff["w_down"]) == (None, "model", "data")
    # embed takes fsdp but never the model axis (no vocab parallelism)
    assert "model" not in tuple(specs["embed"])
    assert "data" in tuple(specs["embed"])
    # tp alone leaves the fsdp dims unsharded
    tp_only = mesh_param_specs(params, mesh, cfg=cfg, tp=True)
    assert tuple(tp_only["stack"]["body"][0]["mixer"]["wq"]) == \
        (None, None, "model")


@pytest.mark.tier0
def test_mesh_param_specs_tp_requires_cfg_and_divisibility():
    mesh = _mesh_stub(data=2, model=2)
    cfg = _reduced("qwen3-1.7b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="cfg"):
        mesh_param_specs(params, mesh, tp=True)
    # heads not divisible by model size -> attention stays replicated
    odd = dataclasses.replace(cfg, n_heads=3, n_kv_heads=3)
    p3 = T.init_params(jax.random.PRNGKey(0), odd)
    specs = mesh_param_specs(p3, mesh, cfg=odd, tp=True)
    mixer = specs["stack"]["body"][0]["mixer"]
    for name in ("wq", "wk", "wv", "wo"):
        assert "model" not in tuple(mixer[name]), (name, mixer[name])


@pytest.mark.tier0
def test_mesh_param_specs_fsdp_without_model_axis():
    """FSDP works on a pure data mesh (no 'model' axis at all)."""
    mesh = _mesh_stub(data=4)
    cfg = _reduced("qwen3-1.7b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    specs = mesh_param_specs(params, mesh, cfg=cfg, fsdp=True)
    w_gate = specs["stack"]["body"][0]["ff"]["w_gate"]
    assert tuple(w_gate) == (None, "data", None), w_gate
    assert "model" not in {e for leaf in jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        for e in tuple(leaf) if e is not None}


@pytest.mark.tier0
def test_fsdp_optimizer_state_bytes_shrink_by_dp_size():
    """The acceptance check for FSDP memory: per-device Adam moment bytes
    drop ~dp_size for an LM config (ratio == dp up to replicated scalars)."""
    from jax.sharding import PartitionSpec as P

    from repro.optim import adam
    from repro.train.parallel import state_bytes_per_device

    cfg = _reduced("qwen3-1.7b")
    mesh = _mesh_stub(data=2, model=2)
    shapes = jax.eval_shape(lambda k: T.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    ost = jax.eval_shape(adam.init, shapes)
    pspecs = mesh_param_specs(shapes, mesh, cfg=cfg, fsdp=True)
    ospecs = adam.AdamState(mu=pspecs, nu=pspecs, step=P())
    full = state_bytes_per_device(ost, jax.tree.map(lambda _: P(), ost),
                                  mesh)
    sharded = state_bytes_per_device(ost, ospecs, mesh)
    ratio = full / sharded
    assert 1.9 < ratio <= 2.0, ratio


# ---------------------------------------------------------------------------
# multi-device subprocess: Megatron-TP and FSDP vs the unsharded step
# ---------------------------------------------------------------------------


TP_FSDP_2D_SCRIPT = textwrap.dedent("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    assert jax.device_count() == 4, jax.device_count()
    from jax.sharding import PartitionSpec as P
    from repro.configs.registry import get_config
    from repro.core import LargeBatchConfig, Regime
    from repro.launch.mesh import make_2d_mesh
    from repro.models import transformer as T
    from repro.optim import adam, sgd
    from repro.train import parallel as PAR
    from repro.train.trainer import make_lm_train_step

    mesh = make_2d_mesh()
    assert dict(mesh.shape) == {"data": 2, "model": 2}, mesh
    lb = LargeBatchConfig(batch_size=8, base_batch_size=8, grad_clip=1.0)
    regime = Regime(base_lr=0.02, total_steps=10, drop_every=5)

    def reduced(arch):
        return dataclasses.replace(get_config(arch).reduced(),
                                   dtype="float32", vocab_size=128)

    def run(cfg, steps=3, use_kernels=False, tp=False, fsdp=False,
            optimizer="sgd"):
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        s1 = jax.jit(make_lm_train_step(cfg, lb, regime,
                                        use_kernels=use_kernels,
                                        optimizer=optimizer))
        s2 = jax.jit(make_lm_train_step(cfg, lb, regime, mesh=mesh,
                                        params=params, tp=tp, fsdp=fsdp,
                                        use_kernels=use_kernels,
                                        optimizer=optimizer))
        p1 = p2 = params
        o1 = o2 = (adam.init(params) if optimizer == "adam"
                   else sgd.init(params))
        for k in range(steps):
            toks = jax.random.randint(
                jax.random.fold_in(jax.random.PRNGKey(1), k), (8, 16),
                0, cfg.vocab_size)
            b = {"tokens": toks}
            p1, o1, m1 = s1(p1, o1, b, jnp.int32(k),
                            jax.random.PRNGKey(2 + k))
            p2, o2, m2 = s2(p2, o2, b, jnp.int32(k),
                            jax.random.PRNGKey(2 + k))
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=1e-5)
        np.testing.assert_allclose(float(m1["grad_norm"]),
                                   float(m2["grad_norm"]), rtol=1e-4)
        for a, c in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       rtol=2e-5, atol=1e-6)
        return p2, o1, o2

    qwen = reduced("qwen3-1.7b")

    # Megatron TP alone: attention heads + dense MLP split over 'model'
    p2, _, _ = run(qwen, tp=True)
    spec = p2["stack"]["body"][0]["mixer"]["wq"].sharding.spec
    assert tuple(spec) == (None, None, "model"), spec
    print("TP_OK")

    # FSDP alone: params + optimizer state sharded over dp
    p2, _, o2 = run(qwen, fsdp=True)
    spec = p2["stack"]["body"][0]["ff"]["w_gate"].sharding.spec
    assert "data" in tuple(spec), spec
    mspec = o2.momentum["stack"]["body"][0]["ff"]["w_gate"].sharding.spec
    assert "data" in tuple(mspec), mspec
    print("FSDP_OK")

    # the full stack: MoE expert sharding + TP attention + FSDP, 3 steps
    run(reduced("kimi-k2-1t-a32b"), tp=True, fsdp=True)
    print("TP_FSDP_MOE_OK")

    # Pallas kernel path under TP+FSDP (1 step for time)
    run(qwen, steps=1, use_kernels=True, tp=True, fsdp=True)
    print("TP_FSDP_KERNELS_OK")

    # adam: shard-local update from dp-scattered grads. Multi-step params
    # are NOT compared — mu_hat/(sqrt(nu_hat)+eps) amplifies fp32
    # reassociation noise into O(lr) drift — but the first moment after one
    # step is linear in the gradients and must match exactly.
    params = T.init_params(jax.random.PRNGKey(0), qwen)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                              qwen.vocab_size)
    b = {"tokens": toks}
    s1 = jax.jit(make_lm_train_step(qwen, lb, regime, optimizer="adam"))
    s2 = jax.jit(make_lm_train_step(qwen, lb, regime, mesh=mesh,
                                    params=params, optimizer="adam",
                                    tp=True, fsdp=True))
    o = adam.init(params)
    _, o1, _ = s1(params, o, b, jnp.int32(0), jax.random.PRNGKey(2))
    _, o2, _ = s2(params, o, b, jnp.int32(0), jax.random.PRNGKey(2))
    for a, c in zip(jax.tree.leaves(o1.mu), jax.tree.leaves(o2.mu)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=2e-5, atol=1e-6)
    # per-device moment memory shrinks ~dp_size under FSDP
    pspecs = PAR.mesh_param_specs(params, mesh, cfg=qwen, fsdp=True)
    ospecs = adam.AdamState(mu=pspecs, nu=pspecs, step=P())
    full = sum(l.nbytes for l in jax.tree.leaves(adam.init(params)))
    per_dev = PAR.state_bytes_per_device(adam.init(params), ospecs, mesh)
    ratio = full / per_dev
    assert 1.9 < ratio <= 2.0, ratio
    print("ADAM_FSDP_OK")
    print("TP_FSDP_2D_OK")
""")


def test_tp_fsdp_2d_matches_single_device_subprocess():
    """(2 data, 2 model): the Megatron-TP step, the FSDP step, and the
    combined TP+FSDP step (dense, MoE, and Pallas-kernel paths) produce
    multi-step params exactly equal to the unsharded step; adam first
    moments match after one step and its per-device state bytes shrink by
    the dp size."""
    proc = _run_multidev(TP_FSDP_2D_SCRIPT)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    for tag in ("TP_OK", "FSDP_OK", "TP_FSDP_MOE_OK", "ADAM_FSDP_OK",
                "TP_FSDP_2D_OK"):
        assert tag in proc.stdout, proc.stdout
