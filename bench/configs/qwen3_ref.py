"""Plain reference of the Qwen3 dense decoder (Qwen/Qwen3-1.7B's
``config.json``): pre-norm blocks of grouped-query attention with RMS-normed
queries and keys (qk-norm) and half-rotation RoPE, a SwiGLU MLP, a final RMS
norm and a head tied to the embedding; next-token cross-entropy; momentum
SGD with global-norm clipping.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST`` with no
kernel; it imports nothing of the program under test. Weights are kept in
the configuration's ``torch_dtype`` (bfloat16; the norms' scales in
float32) and the momentum in float32. The parameter tree has the program's
layout, which is the interface between the two: ``embed`` (padded vocab x
hidden), ``final_norm.scale``, and ``stack.body[0]`` holding every layer's
leaves stacked on a leading layer axis: ``norm1.scale``, ``mixer.{wq, wk,
wv, wo, q_norm.scale, k_norm.scale}``, ``norm2.scale``, ``ff.{w_gate,
w_up, w_down}``.

To fit one chip beside nothing else, a step runs layer by layer and row by
row: the forward keeps each layer's input, the head's loss is taken over
blocks of positions, and the backward runs each layer's vector-Jacobian
product one row at a time. The global norm that clipping needs is known
only after the whole backward, so a step runs the backward twice: once for
the norms, once to apply the update layer by layer.

``compute="fp8"`` is the control: every matmul's operands rounded to
float8 e4m3 with a per-tensor scale, the step below the configuration's
bfloat16.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
POS_BLOCK = 512


def padded_vocab(cfg: Dict) -> int:
    return cfg["layout"]["embed_rows"]


def init(key, cfg: Dict) -> Dict:
    """Seeded weights in the program's layout: normal with std 1/sqrt(fan
    in) for the projections, 0.02 for the embedding, unit norm scales."""
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    ff = cfg["intermediate_size"]
    wdt = jnp.dtype(cfg["torch_dtype"])
    k = iter(jax.random.split(key, 8))

    def w(shape, fan_in):
        return (jax.random.normal(next(k), shape, F32)
                / math.sqrt(fan_in)).astype(wdt)

    body = {
        "norm1": {"scale": jnp.ones((L, d), F32)},
        "mixer": {"wq": w((L, d, h * hd), d), "wk": w((L, d, kv * hd), d),
                  "wv": w((L, d, kv * hd), d), "wo": w((L, h * hd, d), h * hd),
                  "q_norm": {"scale": jnp.ones((L, hd), wdt)},
                  "k_norm": {"scale": jnp.ones((L, hd), wdt)}},
        "norm2": {"scale": jnp.ones((L, d), F32)},
        "ff": {"w_gate": w((L, d, ff), d), "w_up": w((L, d, ff), d),
               "w_down": w((L, ff, d), ff)},
    }
    embed = (0.02 * jax.random.normal(next(k), (padded_vocab(cfg), d), F32)
             ).astype(wdt)
    return {"embed": embed, "final_norm": {"scale": jnp.ones((d,), F32)},
            "stack": {"head": [], "body": [body], "tail": []}}


class Model:
    """The forward pieces, in float32 or (the control) fp8 matmuls."""

    def __init__(self, cfg: Dict, compute: str = "f32"):
        self.cfg = cfg
        self.eps = cfg["rms_norm_eps"]
        self.fp8 = compute == "fp8"

    def _q(self, a):
        a = a.astype(F32)
        if not self.fp8:
            return a
        s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
        q = (a / s).astype(jnp.float8_e4m3fn).astype(F32) * s
        return a + jax.lax.stop_gradient(q - a)   # the cast passes gradients

    def mm(self, spec: str, a, b):
        return jnp.einsum(spec, self._q(a), self._q(b), precision=HIGHEST)

    def rms(self, x, scale):
        x = x.astype(F32)
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + self.eps) * scale.astype(F32)

    def rope(self, x):
        """x: (R, S, heads, hd); positions 0..S-1."""
        hd = x.shape[-1]
        half = hd // 2
        freqs = 1.0 / (self.cfg["rope_theta"]
                       ** (jnp.arange(half, dtype=F32) / half))
        ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * freqs
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

    def layer(self, lp, x):
        """One decoder layer over x: (R, S, d) float32."""
        c = self.cfg
        R, S, _ = x.shape
        h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                     c["head_dim"])
        m = lp["mixer"]
        a = self.rms(x, lp["norm1"]["scale"])
        q = self.mm("rsd,de->rse", a, m["wq"]).reshape(R, S, h, hd)
        k = self.mm("rsd,de->rse", a, m["wk"]).reshape(R, S, kv, hd)
        v = self.mm("rsd,de->rse", a, m["wv"]).reshape(R, S, kv, hd)
        q = self.rope(self.rms(q, m["q_norm"]["scale"]))
        k = self.rope(self.rms(k, m["k_norm"]["scale"]))
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
        s = self.mm("rthd,rshd->rhts", q, k) / math.sqrt(hd)
        causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = self.mm("rhts,rshd->rthd", p, v).reshape(R, S, h * hd)
        x = x + self.mm("rse,ed->rsd", o, m["wo"])
        f = lp["ff"]
        b = self.rms(x, lp["norm2"]["scale"])
        g = jax.nn.silu(self.mm("rsd,df->rsf", b, f["w_gate"]))
        u = self.mm("rsd,df->rsf", b, f["w_up"])
        return x + self.mm("rsf,fd->rsd", g * u, f["w_down"])

    def head_nll(self, fn_scale, embed, x, targets, weights):
        """Summed next-token negative log-likelihood of x: (P, d) against
        targets (P,) with weights (P,), over the real vocabulary."""
        v = self.cfg["vocab_size"]
        logits = self.mm("pd,vd->pv", self.rms(x, fn_scale), embed[:v])
        gold = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(logits, -1) - gold) * weights)

    def head(self, fn_scale, embed, x, toks):
        """Summed loss of the whole batch and its gradients with respect to
        the final norm, the embedding (as the head) and the top hidden
        states, taken over blocks of positions. x: (R, S, d); toks: (R, S)."""
        R, S, d = x.shape
        tgt = jnp.concatenate([toks[:, 1:], jnp.zeros((R, 1), toks.dtype)], 1)
        wts = jnp.broadcast_to(jnp.arange(S) < S - 1, (R, S)).astype(F32)
        block = math.gcd(R * S, POS_BLOCK)
        xb, tb, wb = (a.reshape(R * S // block, block, *a.shape[2:])
                      for a in (x, tgt, wts))
        grad = jax.value_and_grad(self.head_nll, argnums=(0, 1, 2))

        def body(carry, blk):
            loss, g_fn, g_emb = carry
            nll, (gf, ge, gx) = grad(fn_scale, embed, *blk)
            return (loss + nll, g_fn + gf, g_emb + ge), gx

        init = (jnp.zeros((), F32), jnp.zeros(fn_scale.shape, F32),
                jnp.zeros(embed.shape, F32))
        (loss, g_fn, g_emb), gx = jax.lax.scan(body, init, (xb, tb, wb))
        return loss, g_fn, g_emb, gx.reshape(R, S, d)


def _layer_of(body, l):
    return jax.tree.map(lambda a: a[l], body)


def _sq(tree):
    return jax.tree.map(lambda a: jnp.sum(jnp.square(a.astype(F32))), tree)


class Trainer:
    """The recipe's SGD steps on the reference model.

    ``recipe`` holds the traffic file's training keys: ``rows``, ``seq``,
    ``base_lr``, ``lr_rule``, ``base_batch``, ``momentum``,
    ``weight_decay`` and ``grad_clip``. ``loss_rows`` (default: all) takes
    the mean loss over only the first rows of each batch, the fault of a
    step that leaves part of its batch out.
    """

    def __init__(self, cfg: Dict, recipe: Dict, compute: str = "f32",
                 loss_rows: int = 0):
        self.cfg = cfg
        self.r = recipe
        self.model = M = Model(cfg, compute)
        self.loss_rows = loss_rows or recipe["rows"]
        self.n_targets = self.loss_rows * (recipe["seq"] - 1)
        self._layer = jax.jit(M.layer)
        self._layer_vjp = jax.jit(
            lambda lp, x, g: jax.vjp(M.layer, lp, x)[1](g))
        self._head = jax.jit(M.head)
        self._embed_grad = jax.jit(
            lambda g, toks, gx: g.at[toks].add(gx))
        self._sq = jax.jit(_sq)
        self._update_layer = jax.jit(self._upd_layer, donate_argnums=(0, 1))
        self._update = jax.jit(self._upd, donate_argnums=(0, 1))

    def lr(self) -> float:
        r = self.r
        ratio = r["rows"] / r["base_batch"]
        return {"sqrt": r["base_lr"] * math.sqrt(ratio),
                "linear": r["base_lr"] * ratio,
                "none": r["base_lr"]}[r["lr_rule"]]

    def _step_leaf(self, p, m, g, scale, lr):
        r = self.r
        g = g.astype(F32) * scale + r["weight_decay"] * p.astype(F32)
        m = r["momentum"] * m + g
        return (p.astype(F32) - lr * m).astype(p.dtype), m

    def _upd_layer(self, body, mom, g, l, scale, lr):
        def one(P, Mo, G):
            p, m = self._step_leaf(P[l], Mo[l], G, scale, lr)
            return P.at[l].set(p), Mo.at[l].set(m)
        out = jax.tree.map(one, body, mom, g)
        return (jax.tree.map(lambda o: o[0], out, is_leaf=_pair),
                jax.tree.map(lambda o: o[1], out, is_leaf=_pair))

    def _upd(self, p, m, g, scale, lr):
        out = jax.tree.map(lambda p, m, g: self._step_leaf(p, m, g, scale, lr),
                           p, m, g)
        return (jax.tree.map(lambda o: o[0], out, is_leaf=_pair),
                jax.tree.map(lambda o: o[1], out, is_leaf=_pair))

    def _backward(self, layer_params, xs, gtop, emit):
        """Layer vector-Jacobian products from the top down, a row at a
        time; ``emit(l, grads of layer l)`` follows each layer's, and
        ``layer_params(l)`` gives the layer's weights as they are then.
        Returns the gradient of the embedded input."""
        gx = gtop
        for l in reversed(range(self.cfg["num_hidden_layers"])):
            lp = layer_params(l)
            g_l, rows = None, []
            for r in range(gx.shape[0]):
                gp, gr = self._layer_vjp(lp, xs[l][r:r + 1], gx[r:r + 1])
                g_l = gp if g_l is None else jax.tree.map(jnp.add, g_l, gp)
                rows.append(gr)
            gx = jnp.concatenate(rows)
            emit(l, g_l)
        return gx

    def step(self, params, mom, tokens: np.ndarray, first: bool):
        """One SGD step; returns (params, momentum, loss, readings)."""
        cfg, L = self.cfg, self.cfg["num_hidden_layers"]
        toks = jnp.asarray(tokens[:self.loss_rows])
        body = params["stack"]["body"][0]
        x = params["embed"][toks].astype(F32)
        xs = [x]
        for l in range(L):
            lp = _layer_of(body, l)
            x = jnp.concatenate([self._layer(lp, x[r:r + 1])
                                 for r in range(x.shape[0])])
            xs.append(x)
        inv = 1.0 / self.n_targets
        loss, g_fn, g_emb, gtop = self._head(params["final_norm"]["scale"],
                                             params["embed"], xs[L], toks)
        loss, g_fn, g_emb, gtop = float(loss), g_fn * inv, g_emb * inv, \
            gtop * inv

        # pass 1: norms of every leaf's gradient
        sq_layers = {}
        gx0 = self._backward(lambda l: _layer_of(body, l), xs, gtop,
                             lambda l, g: sq_layers.__setitem__(
                                 l, self._sq(g)))
        g_emb = self._embed_grad(g_emb, toks, gx0)
        sq_body = jax.tree.map(lambda *a: sum(a), *sq_layers.values())
        sq = {"embed": self._sq(g_emb), "final_norm": {"scale": self._sq(
            g_fn)}, "stack": {"head": [], "body": [sq_body], "tail": []}}
        gnorm = math.sqrt(sum(float(v) for v in jax.tree.leaves(sq)))
        clip = self.r["grad_clip"]
        scale = min(1.0, clip / max(gnorm, 1e-12)) if clip > 0 else 1.0
        lr = self.lr()

        # pass 2: the update, layer by layer
        mb = mom["stack"]["body"][0]

        def apply(l, g):
            nonlocal body, mb
            body, mb = self._update_layer(body, mb, g, l, F32(scale), F32(lr))

        self._backward(lambda l: _layer_of(body, l), xs, gtop, apply)
        del xs
        rest_p = {"embed": params["embed"], "final_norm": params["final_norm"]}
        rest_m = {"embed": mom["embed"], "final_norm": mom["final_norm"]}
        rest_g = {"embed": g_emb, "final_norm": {"scale": g_fn}}
        rest_p, rest_m = self._update(rest_p, rest_m, rest_g, F32(scale),
                                      F32(lr))
        stack = lambda b: {"head": [], "body": [b], "tail": []}  # noqa: E731
        params = dict(rest_p, stack=stack(body))
        mom = dict(rest_m, stack=stack(mb))
        read = {}
        if first:
            read["grad1"] = [math.sqrt(float(v))
                             for v in jax.tree.leaves(sq)]
            read["mom1"] = leaf_norms(mom)
        return params, mom, loss * inv, read

    def run(self, params, batches) -> Dict[str, Any]:
        """One step per token batch from ``params``: each step's loss, the
        per-leaf norms of the first raw gradient and of the first step's
        momentum, and of the parameters' change over all the steps."""
        p0 = jax.device_get(params)
        mom = jax.tree.map(lambda a: jnp.zeros(a.shape, F32), params)
        out: Dict[str, Any] = {"loss": []}
        for i, toks in enumerate(batches):
            params, mom, loss, read = self.step(params, mom, toks, i == 0)
            out["loss"].append(loss)
            out.update(read)
        del mom
        out["change"] = [float(np.sqrt(np.sum(np.square(
            np.asarray(a, np.float32) - np.asarray(b, np.float32)))))
            for a, b in zip(jax.tree.leaves(jax.device_get(params)),
                            jax.tree.leaves(p0))]
        return out


def _pair(x):
    return isinstance(x, tuple)


def leaf_norms(tree) -> List[float]:
    return [float(v) for v in jax.device_get(jax.tree.leaves(jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(F32)))), tree)))]


class Scorer:
    """Teacher-forced next-token logits of the reference over a prompt and
    the tokens served after it, every sequence right-padded to ``length``
    (causal, so the padding changes nothing before it), layer by layer.
    ``compute="fp8"`` is the control."""

    def __init__(self, cfg: Dict, length: int, max_new: int,
                 compute: str = "f32"):
        self.cfg = cfg
        self.length, self.max_new = length, max_new
        M = Model(cfg, compute)
        v = cfg["vocab_size"]
        self._layer = jax.jit(M.layer)
        self._logits = jax.jit(lambda fn, embed, x, pos: M.mm(
            "pd,vd->pv", M.rms(x[0, pos], fn), embed[:v]))

    def logits(self, params, prompt: np.ndarray, served: np.ndarray):
        """(len(served), vocab) logits: row j predicts ``served[j]``."""
        seq = np.zeros((1, self.length), np.int32)
        seq[0, :len(prompt)] = prompt
        seq[0, len(prompt):len(prompt) + len(served)] = served
        body = params["stack"]["body"][0]
        x = params["embed"][jnp.asarray(seq)].astype(F32)
        for l in range(self.cfg["num_hidden_layers"]):
            x = self._layer(_layer_of(body, l), x)
        pos = np.full((self.max_new,), len(prompt) - 1, np.int32)
        pos[:len(served)] += np.arange(len(served), dtype=np.int32)
        out = self._logits(params["final_norm"]["scale"], params["embed"],
                           x, jnp.asarray(pos))
        return out[:len(served)]


def served_gaps(ref_logits, tokens) -> np.ndarray:
    """How far each served token's reference logit lies below the
    reference's best."""
    ref_logits = np.asarray(ref_logits)
    pick = ref_logits[np.arange(len(tokens)), np.asarray(tokens)]
    return ref_logits.max(-1) - pick
