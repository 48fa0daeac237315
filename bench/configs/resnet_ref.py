"""Plain reference of the paper's residual networks under the paper's
training recipe: ghost batch norm (Hoffer et al. 2017, Algorithm 1) and
momentum SGD with global-norm clipping and weight decay.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``, with no
kernel; it imports nothing of the program under test. The parameter tree
has the program's layout, which is the interface between the two:
``{"stem": {"w", "norm": {"gamma", "beta"}}, "stages": [[block, ...], ...],
"out": {"w", "b"}}``, a block being ``{"w1", "norm1", "w2", "norm2"[,
"proj"]}``, convolutions HWIO, images NHWC.

A step's gradient is the sum over blocks of rows (whole ghost batches), so
the reference holds one block's activations at a time.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def init(key, cfg: Dict) -> tuple:
    """Seeded weights and running state in the program's layout: He-normal
    convolutions, a 1/sqrt(fan-in) classifier, unit gamma, zero beta."""
    def conv(k, kh, cin, cout):
        return jax.random.normal(k, (kh, kh, cin, cout)) * math.sqrt(
            2.0 / (kh * kh * cin))

    def norm(c):
        return ({"gamma": jnp.ones((c,)), "beta": jnp.zeros((c,))},
                {"mu_run": jnp.zeros((c,)), "var_run": jnp.ones((c,)),
                 "initialized": jnp.zeros((), jnp.bool_)})

    keys = iter(jax.random.split(key, 4 * len(cfg["channels"])
                                 * cfg["blocks_per_stage"] + 4))
    c0 = cfg["channels"][0]
    stem_n, stem_s = norm(c0)
    params = {"stem": {"w": conv(next(keys), 3, cfg["input_shape"][2], c0),
                       "norm": stem_n}, "stages": []}
    state = {"stem": stem_s, "stages": []}
    cin = c0
    for cout in cfg["channels"]:
        sp, ss = [], []
        for _ in range(cfg["blocks_per_stage"]):
            n1, s1 = norm(cout)
            n2, s2 = norm(cout)
            blk = {"w1": conv(next(keys), 3, cin, cout), "norm1": n1,
                   "w2": conv(next(keys), 3, cout, cout), "norm2": n2}
            if cin != cout:
                blk["proj"] = conv(next(keys), 1, cin, cout)
            sp.append(blk)
            ss.append({"norm1": s1, "norm2": s2})
            cin = cout
        params["stages"].append(sp)
        state["stages"].append(ss)
    params["out"] = {"w": jax.random.normal(next(keys), (cin, cfg["n_classes"]))
                     / math.sqrt(cin),
                     "b": jnp.zeros((cfg["n_classes"],))}
    return params, state


def _conv(x, w, stride, dtype):
    """A "SAME" convolution as a matmul over its input patches (XLA
    compiles a HIGHEST-precision convolution for the TPU very slowly)."""
    kh, kw, cin, cout = w.shape
    b, h, wd, _ = x.shape
    ho, wo = -(-h // stride), -(-wd // stride)
    ph = max((ho - 1) * stride + kh - h, 0)
    pw = max((wo - 1) * stride + kw - wd, 0)
    xp = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                     (pw // 2, pw - pw // 2), (0, 0)))
    patches = jnp.concatenate(
        [xp[:, i:i + stride * (ho - 1) + 1:stride,
            j:j + stride * (wo - 1) + 1:stride, :]
         for i in range(kh) for j in range(kw)], axis=-1)
    return jnp.dot(patches, w.astype(dtype).reshape(kh * kw * cin, cout),
                   precision=HIGHEST)


def _norm(x, p, ghost: int, eps: float):
    """Batch norm with statistics per ghost batch of ``ghost`` consecutive
    rows; two-pass variance."""
    b, h, w, c = x.shape
    xg = x.reshape(b // ghost, ghost * h * w, c)
    mu = xg.mean(axis=1, keepdims=True)
    var = jnp.square(xg - mu).mean(axis=1, keepdims=True)
    y = (xg - mu) / jnp.sqrt(var + eps)
    y = y * p["gamma"].astype(x.dtype) + p["beta"].astype(x.dtype)
    return y.reshape(b, h, w, c)


def _block(x, blk, stride, ghost, eps, dtype):
    h = jax.nn.relu(_norm(_conv(x, blk["w1"], stride, dtype), blk["norm1"],
                          ghost, eps))
    h = _norm(_conv(h, blk["w2"], 1, dtype), blk["norm2"], ghost, eps)
    skip = _conv(x, blk["proj"], stride, dtype) if "proj" in blk else x
    return jax.nn.relu(h + skip)


def logits(params, cfg: Dict, x, ghost: int, dtype=jnp.float32):
    """The network's logits; a stage's blocks after its first share their
    shapes and run as one scan."""
    eps = cfg["bn_eps"]
    x = x.astype(dtype)
    x = jax.nn.relu(_norm(_conv(x, params["stem"]["w"], 1, dtype),
                          params["stem"]["norm"], ghost, eps))
    for si, stage in enumerate(params["stages"]):
        x = _block(x, stage[0], 2 if si > 0 else 1, ghost, eps, dtype)
        if len(stage) > 1:
            rest = jax.tree.map(lambda *a: jnp.stack(a), *stage[1:])
            x, _ = jax.lax.scan(
                lambda x, blk: (_block(x, blk, 1, ghost, eps, dtype), None),
                x, rest)
    feat = x.mean(axis=(1, 2))
    return (jnp.dot(feat, params["out"]["w"].astype(dtype), precision=HIGHEST)
            + params["out"]["b"].astype(dtype))


def _nll_sum(params, cfg, x, y, ghost, dtype):
    logp = jax.nn.log_softmax(logits(params, cfg, x, ghost, dtype)
                              .astype(jnp.float32))
    return -jnp.take_along_axis(logp, y[:, None], axis=1).sum()


def leaf_norms(tree) -> List[float]:
    return [float(v) for v in jax.device_get(jax.tree.leaves(jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))),
        tree)))]


class Trainer:
    """The recipe's SGD steps on the reference model.

    ``recipe`` holds the traffic file's training keys: ``batch``,
    ``ghost`` and ``use_gbn``, ``base_batch``, ``lr_rule``, ``base_lr``,
    ``drop_every``, ``drop_factor``, ``regime_adaptation``, ``momentum``,
    ``weight_decay``, ``grad_clip`` and ``reference_rows`` (rows per
    block). ``dtype`` is the precision the model computes and keeps its
    weights and momentum in: float32 for the reference, bfloat16 for its
    control. ``loss_rows`` (default: the
    batch) takes the mean loss over only the first rows of each batch, the
    fault of a step that leaves part of its batch out.
    """

    def __init__(self, cfg: Dict, recipe: Dict, dtype=jnp.float32,
                 loss_rows: int = 0):
        self.cfg = cfg
        self.r = recipe
        batch = self.loss_rows = loss_rows or recipe["batch"]
        self.ghost = recipe["ghost"] if recipe["use_gbn"] else batch
        self.rows = max(self.ghost, min(recipe["reference_rows"], batch))
        if batch % self.rows or self.rows % self.ghost:
            raise ValueError("reference blocks must hold whole ghost batches")
        self.dtype = dtype
        self._grad = jax.jit(jax.value_and_grad(self._block_loss))
        self._update = jax.jit(self._sgd)

    def _block_loss(self, params, x, y):
        return _nll_sum(params, self.cfg, x, y, self.ghost,
                        self.dtype) / self.loss_rows

    def lr_at(self, step: int) -> float:
        r = self.r
        ratio = r["batch"] / r["base_batch"]
        lr = {"sqrt": r["base_lr"] * math.sqrt(ratio),
              "linear": r["base_lr"] * ratio,
              "none": r["base_lr"]}[r["lr_rule"]]
        every = r["drop_every"]
        if r["regime_adaptation"]:
            every = max(1, int(round(every * ratio)))
        return lr * r["drop_factor"] ** (step // every)

    def _sgd(self, params, mom, grads, lr):
        r = self.r
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                             for g in jax.tree.leaves(grads)))
        scale = (jnp.minimum(1.0, r["grad_clip"] / jnp.maximum(gnorm, 1e-12))
                 if r["grad_clip"] > 0 else 1.0)
        g = jax.tree.map(lambda g, p: (g * scale).astype(p.dtype)
                         + r["weight_decay"] * p, grads, params)
        mom = jax.tree.map(lambda m, g: r["momentum"] * m + g, mom, g)
        params = jax.tree.map(lambda p, m: p - lr.astype(p.dtype) * m,
                              params, mom)
        return params, mom

    def gradient(self, params, x: np.ndarray, y: np.ndarray):
        """Loss and gradient of one step's batch, block by block."""
        loss, grads = 0.0, None
        for lo in range(0, self.loss_rows, self.rows):
            l, g = self._grad(params, jnp.asarray(x[lo:lo + self.rows]),
                              jnp.asarray(y[lo:lo + self.rows]))
            loss = loss + l
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        return float(loss), grads

    def run(self, params, batches) -> Dict[str, Any]:
        """Take one step per (x, y) batch from ``params``. Returns each
        step's loss, the per-leaf norms of the first raw gradient and of
        the first step's momentum (the gradient as the optimizer applies
        it), and of the change of the parameters over all the steps."""
        p0 = params
        params = jax.tree.map(lambda a: a.astype(self.dtype), params)
        mom = jax.tree.map(jnp.zeros_like, params)
        out: Dict[str, Any] = {"loss": []}
        for i, (x, y) in enumerate(batches):
            loss, grads = self.gradient(params, x, y)
            params, mom = self._update(params, mom, grads,
                                       jnp.float32(self.lr_at(i)))
            out["loss"].append(loss)
            if i == 0:
                out["grad1"] = leaf_norms(grads)
                out["mom1"] = leaf_norms(mom)
        out["change"] = leaf_norms(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b, params, p0))
        return out
