"""The paper's convolutional models: C1/C3-style shallow convnets (Keskar et
al. 2017) and ResNet44 / WResNet-style residual networks (He et al. 2016;
Zagoruyko 2016), all with (ghost) batch normalization — the models behind
Table 1 and Figures 1-3.

NHWC layout; BN statistics reduce over (ghost-batch, H, W) per channel.

Named scopes (HLO ``op_name`` metadata, read from a device trace by
``bench/scopes.py``): ``stage{s}``/``block{b}`` around the residual stages
and blocks, and one sublayer scope around each piece of work: ``conv``
(every convolution, the stem and the projection shortcut included),
``gbn`` (set in :mod:`repro.core.gbn`), ``act`` (the ReLU with the
residual join or max pool beside it) and ``head`` (pool and classifier).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.paper_models import VisionModelConfig
from repro.models.vision_common import norm_apply, norm_init

Params = Dict[str, Any]


def _conv_init(rng, kh, kw, cin, cout):
    fan_in = kh * kw * cin
    w = jax.random.normal(rng, (kh, kw, cin, cout)) * math.sqrt(2.0 / fan_in)
    return w


@jax.named_scope("conv")
def _conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


@jax.named_scope("head")
def _head(params, x):
    """Global average pool, then the linear classifier."""
    return x.mean(axis=(1, 2)) @ params["w"] + params["b"]


@jax.named_scope("act")
def _act(x, skip=None):
    """ReLU, after the residual join where there is a ``skip``."""
    return jax.nn.relu(x if skip is None else x + skip)


@jax.named_scope("act")
def _maxpool2(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


# ---------------------------------------------------------------------------
# C1/C3-style shallow convnet
# ---------------------------------------------------------------------------


def convnet_init(rng, cfg: VisionModelConfig) -> Tuple[Params, Params]:
    params: Params = {"stages": [], "out": None}
    state: Params = {"stages": []}
    cin = cfg.input_shape[2]
    for i, cout in enumerate(cfg.channels):
        r = jax.random.fold_in(rng, i)
        np_, ns = norm_init(cfg, cout)
        params["stages"].append({
            "w": _conv_init(r, 3, 3, cin, cout),
            "norm": np_,
        })
        state["stages"].append(ns)
        cin = cout
    feat = cin
    params["out"] = {
        "w": jax.random.normal(jax.random.fold_in(rng, 777),
                               (feat, cfg.n_classes)) / math.sqrt(feat),
        "b": jnp.zeros((cfg.n_classes,)),
    }
    return params, state


def convnet_apply(params: Params, state: Params, cfg: VisionModelConfig,
                  x: jax.Array, *, training: bool = True,
                  ghost_batch_size: Optional[int] = None,
                  use_gbn: Optional[bool] = None,
                  use_kernels: bool = False) -> Tuple[jax.Array, Params]:
    new_state: Params = {"stages": []}
    for si, (sp, ss) in enumerate(zip(params["stages"], state["stages"])):
        with jax.named_scope(f"stage{si}"):
            x = _conv(x, sp["w"])
            x, ns = norm_apply(cfg, sp["norm"], ss, x, training=training,
                               ghost_batch_size=ghost_batch_size,
                               use_gbn=use_gbn, use_kernels=use_kernels)
            new_state["stages"].append(ns)
            x = _act(x)
            if x.shape[1] > 2:
                x = _maxpool2(x)
    return _head(params["out"], x), new_state


# ---------------------------------------------------------------------------
# ResNet44 / WResNet16-4 style residual network
# ---------------------------------------------------------------------------


def resnet_init(rng, cfg: VisionModelConfig) -> Tuple[Params, Params]:
    params: Params = {"stem": None, "stages": [], "out": None}
    state: Params = {"stem": None, "stages": []}
    c0 = cfg.channels[0]
    params["stem"] = {"w": _conv_init(jax.random.fold_in(rng, 0), 3, 3,
                                      cfg.input_shape[2], c0)}
    np_, ns = norm_init(cfg, c0)
    params["stem"]["norm"] = np_
    state["stem"] = ns
    cin = c0
    for si, cout in enumerate(cfg.channels):
        stage_p, stage_s = [], []
        for bi in range(cfg.blocks_per_stage):
            r = jax.random.fold_in(rng, 100 * (si + 1) + bi)
            r1, r2, r3 = jax.random.split(r, 3)
            n1p, n1s = norm_init(cfg, cout)
            n2p, n2s = norm_init(cfg, cout)
            blk = {
                "w1": _conv_init(r1, 3, 3, cin, cout),
                "norm1": n1p,
                "w2": _conv_init(r2, 3, 3, cout, cout),
                "norm2": n2p,
            }
            if cin != cout:
                blk["proj"] = _conv_init(r3, 1, 1, cin, cout)
            stage_p.append(blk)
            stage_s.append({"norm1": n1s, "norm2": n2s})
            cin = cout
        params["stages"].append(stage_p)
        state["stages"].append(stage_s)
    params["out"] = {
        "w": jax.random.normal(jax.random.fold_in(rng, 888),
                               (cin, cfg.n_classes)) / math.sqrt(cin),
        "b": jnp.zeros((cfg.n_classes,)),
    }
    return params, state


def resnet_apply(params: Params, state: Params, cfg: VisionModelConfig,
                 x: jax.Array, *, training: bool = True,
                 ghost_batch_size: Optional[int] = None,
                 use_gbn: Optional[bool] = None,
                 use_kernels: bool = False) -> Tuple[jax.Array, Params]:
    kw = dict(training=training, ghost_batch_size=ghost_batch_size,
              use_gbn=use_gbn, use_kernels=use_kernels)
    new_state: Params = {"stem": None, "stages": []}
    x = _conv(x, params["stem"]["w"])
    x, ns = norm_apply(cfg, params["stem"]["norm"], state["stem"], x, **kw)
    new_state["stem"] = ns
    x = _act(x)
    for si, (stage_p, stage_s) in enumerate(zip(params["stages"],
                                                state["stages"])):
        ns_stage = []
        for bi, (blk, bs) in enumerate(zip(stage_p, stage_s)):
            stride = 2 if (si > 0 and bi == 0) else 1
            with jax.named_scope(f"stage{si}"), \
                    jax.named_scope(f"block{bi}"):
                h = _conv(x, blk["w1"], stride=stride)
                h, n1 = norm_apply(cfg, blk["norm1"], bs["norm1"], h, **kw)
                h = _act(h)
                h = _conv(h, blk["w2"])
                h, n2 = norm_apply(cfg, blk["norm2"], bs["norm2"], h, **kw)
                skip = x
                if "proj" in blk:
                    skip = _conv(x, blk["proj"], stride=stride)
                elif stride != 1:
                    skip = x[:, ::stride, ::stride, :]
                x = _act(h, skip)
            ns_stage.append({"norm1": n1, "norm2": n2})
        new_state["stages"].append(ns_stage)
    return _head(params["out"], x), new_state


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------


def init(rng, cfg: VisionModelConfig) -> Tuple[Params, Params]:
    if cfg.kind == "convnet":
        return convnet_init(rng, cfg)
    if cfg.kind == "resnet":
        return resnet_init(rng, cfg)
    raise ValueError(cfg.kind)


def apply(params, state, cfg, x, **kw):
    if cfg.kind == "convnet":
        return convnet_apply(params, state, cfg, x, **kw)
    if cfg.kind == "resnet":
        return resnet_apply(params, state, cfg, x, **kw)
    raise ValueError(cfg.kind)


def model_fns(cfg: VisionModelConfig):
    """Returns (init, apply) for any paper model config (mlp included)."""
    if cfg.kind == "mlp":
        from repro.models import mlp as M
        return M.init, M.apply
    return init, apply
