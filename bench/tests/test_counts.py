"""The benchmark's flop and byte counters: against shapes worked by hand,
and the whole steps' flops against the program's own HLO accounting
(``launch/hlo_analysis.py``) of small CPU compiles."""
import copy

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.counts import least_seconds, lm, vision
from bench.tests import tiny


@pytest.fixture(scope="module")
def vcfg():
    cfg = copy.deepcopy(harness.load_cell("resnet44.lb4096-gbn").config)
    cfg.update(tiny.TINY_VISION)
    return cfg


@pytest.fixture(scope="module")
def lcfg():
    cfg = copy.deepcopy(harness.load_cell("qwen3-1.7b.train-8x2048").config)
    cfg.update(tiny.TINY_LM)
    return cfg


def test_resnet_flops_by_hand(vcfg):
    # stem 13824; stage 1: 2 x 18432; stage 2: 9216 + 18432 + 1024 (proj);
    # stage 3: 9216 + 18432 + 1024; classifier 2 * 16 * 10
    assert vision.forward_flops_per_image(vcfg) == 108352
    assert vision.train_flops_per_image(vcfg) == 3 * 108352 - 13824


def test_resnet44_published_sizes():
    cfg = harness.load_cell("resnet44.lb4096-gbn").config
    convs, norms, dense = vision.layers(cfg)
    assert len(convs) == 1 + 2 * 21 + 2 and len(norms) == 43
    assert dense == (64, 10)
    # ~0.58 GFLOP an image for a training step, 2.39 TFLOP at batch 4096
    assert vision.train_flops_per_image(cfg) * 4096 == pytest.approx(
        2.391e12, rel=1e-3)


def test_gbn_kernel_counts_by_hand(vcfg):
    # 1152 normalised elements an image: 3 x 8x8x4 + 2 x 4x4x8 + 2 x 2x2x16
    got = vision.gbn_step(vcfg, 16)
    assert got["calls"] == 7 * 4
    assert got["bytes"] == 16 * 1152 * 4 * (1 + 2 + 2 + 3)
    assert got["flops"] == 16 * 1152 * (3 + 4 + 5 + 8)


def test_lm_counts_by_hand(lcfg):
    assert lm.matmul_params_per_layer(lcfg) == 147456
    assert lm.forward_flops_per_token(lcfg, 10) == 2 * (
        2 * 147456 + 4 * 4 * 32 * 10) + 2 * 128 * 500
    assert lm.train_flops_per_token(lcfg, 64) == 3 * (
        lm.forward_flops_per_token(lcfg, 32))
    assert lm.prefill_flops(lcfg, 10) == 2 * (
        2 * 147456 * 10 + 2 * 4 * 32 * 100) + 2 * 128 * 500
    fa = lm.flash_attention(lcfg, 2, 64)
    assert fa["fwd"]["flops"] == 2 * 2 * 4 * 32 * 64 * 64
    assert fa["bwd"]["flops"] == 2.5 * fa["fwd"]["flops"]
    assert fa["fwd"]["bytes"] == 2 * 32768 + 32768 + 2048


def test_least_seconds_takes_the_larger_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert least_seconds(1000, 50, peaks) == 10.0
    assert least_seconds(100, 50, peaks) == 5.0


def _hlo_flops(fn, *args) -> float:
    from repro.launch.hlo_analysis import analyze
    return analyze(jax.jit(fn).lower(*args).compile().as_text()).flops


def test_resnet44_step_flops_match_the_hlo():
    from repro.configs.paper_models import RESNET44_CIFAR10
    from repro.core import presets
    from repro.models.cnn import model_fns
    from repro.train.trainer import make_vision_loss_fn
    cfg = harness.load_cell("resnet44.lb4096-gbn").config
    init_fn, apply_fn = model_fns(RESNET44_CIFAR10)
    params, bn = init_fn(jax.random.PRNGKey(0), RESNET44_CIFAR10)
    loss = make_vision_loss_fn(apply_fn, RESNET44_CIFAR10,
                               presets(8, ghost=4)["LB+LR+GBN+RA"])
    x = jnp.ones((8, 32, 32, 3))
    y = jnp.zeros((8,), jnp.int32)
    got = _hlo_flops(jax.grad(lambda p: loss(p, bn, x, y)[0]), params)
    # XLA takes the input gradient of a stride-2 convolution as a
    # convolution over the zero-dilated input, four times the algorithm's
    # work: 3x the forward of the two strided 3x3 convolutions and the two
    # strided projections is the HLO's excess
    strided = (2 * 16 * 16 * 16 * 32 * 9 + 2 * 16 * 16 * 16 * 32
               + 2 * 8 * 8 * 32 * 64 * 9 + 2 * 8 * 8 * 32 * 64)
    assert got == pytest.approx(
        8 * (vision.train_flops_per_image(cfg) + 3 * strided), rel=1e-6)


def test_the_reference_does_the_counted_work():
    """The plain reference computes convolutions as patch matmuls: its HLO
    holds exactly the counted flops."""
    from bench.configs import resnet_ref
    cfg = harness.load_cell("resnet44.lb4096-gbn").config
    params, _ = resnet_ref.init(jax.random.PRNGKey(0), cfg)
    x = jnp.ones((8, 32, 32, 3))
    y = jnp.zeros((8,), jnp.int32)
    got = _hlo_flops(jax.grad(lambda p: resnet_ref._nll_sum(
        p, cfg, x, y, 4, jnp.float32)), params)
    assert got == pytest.approx(8 * vision.train_flops_per_image(cfg),
                                rel=1e-6)


def test_lm_step_flops_match_the_hlo(lcfg):
    from bench.drivers.train_lm import model_config
    from repro.models import transformer as T
    from bench.configs import qwen3_ref
    mcfg = model_config(lcfg)
    params = qwen3_ref.init(jax.random.PRNGKey(0), lcfg)
    toks = jnp.zeros((2, 64), jnp.int32)
    got = _hlo_flops(jax.grad(lambda p: T.lm_loss(
        p, mcfg, {"tokens": toks})[0]), params)
    # the plain path computes full (masked) attention: every token attends
    # to the whole sequence; and the head runs over the padded vocabulary
    want = 2 * 64 * 3 * (lm.forward_flops_per_token(lcfg, 64)
                         + 2 * 128 * (512 - 500))
    assert got == pytest.approx(want, rel=0.01)
