"""Layer 2: trace auditor — lower the hot jitted entry points and assert
tracing-level invariants that the AST lint cannot see.

For every entry in :data:`ENTRIES` (vision/LM train step, decode step,
fused prefill, paged flash-decode) the auditor builds reduced-size real
arguments, traces the function, and checks:

- **no host callbacks** (``trace-callback``): no ``*_callback`` /
  ``outside_call`` primitive anywhere in the jaxpr (recursing into scan /
  cond / custom-vjp sub-jaxprs). A stray ``jax.debug.print`` or
  ``pure_callback`` in a decode loop serialises every step on the host.
- **no f64 promotion** (``trace-f64``): no equation output carries
  ``float64``/``complex128``. With x64 disabled this is belt-and-braces;
  with it enabled (some debugging flows) a bare Python float in the wrong
  place silently doubles every buffer downstream.
- **donation actually aliased** (``trace-donation``): compile with the
  entry's ``donate_argnums`` and require one ``input_output_alias`` header
  entry per donated flat leaf (via
  :func:`repro.launch.hlo_analysis.parse_input_output_aliases`), with no
  "donated buffer unused" warnings. Donation that silently fails to alias
  doubles the optimizer-state working set — invisible until OOM.
- **recompile-hazard census** (``recompile-hazard``): each entry declares
  the static knobs that multiply its compile-cache entries
  (``use_kernels`` x sampling mode x ...); the declared variant product
  must stay within the entry's budget. New static axes must be accounted
  for here, which is the point.

Entries are lazy: each ``build()`` imports and constructs on demand, so
``python -m repro.analysis --lint`` never pays for model init.
"""
from __future__ import annotations

import dataclasses
import math
import warnings
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.findings import Finding

_BAD_DTYPES = ("float64", "complex128")


@dataclass
class Built:
    """A concrete traceable entry: fn + reduced-size real args."""
    fn: Callable
    args: tuple
    donate_argnums: Tuple[int, ...] = ()


@dataclass
class Entry:
    name: str
    path: str                    # repo-relative source the finding points at
    build: Callable[[], Built]
    compile_check: bool = True   # False: jaxpr-only (Pallas entries — the
    #                              TPU kernel path doesn't XLA-compile here)
    static_knobs: dict = field(default_factory=dict)   # knob -> n variants
    variant_budget: int = 8


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------


def _sub_jaxprs(params: dict):
    """Yield every Jaxpr nested in an eqn's params (scan/cond/custom-vjp/
    pjit bodies), whatever key it hides under."""
    for v in params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for x in vs:
            if hasattr(x, "jaxpr"):        # ClosedJaxpr
                yield x.jaxpr
            elif hasattr(x, "eqns"):       # raw Jaxpr
                yield x


def iter_eqns(jaxpr):
    """All equations in ``jaxpr``, recursing into nested sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


# every primitive that calls back into the host Python process mid-program
# (jax 0.9 names; ``jax.debug.print`` lowers to ``debug_print``)
HOST_CALLBACK_PRIMITIVES = frozenset({
    "debug_print", "debug_callback", "pure_callback", "io_callback",
    "callback", "outside_call",
})


def audit_jaxpr(fn: Callable, args: tuple, *, name: str, path: str
                ) -> List[Finding]:
    """Callback + f64 audit on the traced jaxpr of ``fn(*args)``."""
    out: List[Finding] = []
    closed = jax.make_jaxpr(fn)(*args)
    bad_dtypes = set()
    callbacks = set()
    for eqn in iter_eqns(closed.jaxpr):
        pname = eqn.primitive.name
        if pname in HOST_CALLBACK_PRIMITIVES:
            callbacks.add(pname)
        for v in eqn.outvars:
            dt = str(getattr(v.aval, "dtype", ""))
            if dt in _BAD_DTYPES:
                bad_dtypes.add((pname, dt))
    for pname in sorted(callbacks):
        out.append(Finding(path, 0, "trace-callback",
                           f"{name}: host callback primitive '{pname}' "
                           "in the traced program"))
    for pname, dt in sorted(bad_dtypes):
        out.append(Finding(path, 0, "trace-f64",
                           f"{name}: '{pname}' produces {dt} — check for "
                           "accidental wide promotion"))
    return out


def audit_donation(fn: Callable, args: tuple,
                   donate_argnums: Sequence[int], *, name: str, path: str
                   ) -> List[Finding]:
    """Compile with donation and assert the alias header covers every
    donated flat leaf."""
    from repro.launch.hlo_analysis import parse_input_output_aliases
    out: List[Finding] = []
    n_leaves = sum(len(jax.tree.leaves(args[i])) for i in donate_argnums)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        compiled = jax.jit(fn, donate_argnums=tuple(donate_argnums)
                           ).lower(*args).compile()
    for w in caught:
        if "donat" in str(w.message).lower():
            out.append(Finding(path, 0, "trace-donation",
                               f"{name}: {w.message}"))
    aliases = parse_input_output_aliases(compiled.as_text())
    if len(aliases) < n_leaves:
        out.append(Finding(
            path, 0, "trace-donation",
            f"{name}: {n_leaves} donated leaves but only {len(aliases)} "
            "input_output_alias entries — donation not fully aliased"))
    return out


def audit_variants(entry: Entry) -> List[Finding]:
    n = math.prod(entry.static_knobs.values()) if entry.static_knobs else 1
    if n > entry.variant_budget:
        knobs = " x ".join(f"{k}:{v}" for k, v in entry.static_knobs.items())
        return [Finding(entry.path, 0, "recompile-hazard",
                        f"{entry.name}: {n} static-arg variants ({knobs}) "
                        f"> budget {entry.variant_budget}")]
    return []


# ---------------------------------------------------------------------------
# entry registry
# ---------------------------------------------------------------------------


def _vision_train_step() -> Built:
    from repro.configs.paper_models import F1_MNIST
    from repro.core import LargeBatchConfig, Regime
    from repro.models.cnn import model_fns
    from repro.optim import sgd
    from repro.train.trainer import make_vision_train_step

    cfg = dataclasses.replace(F1_MNIST, input_shape=(8, 8, 1),
                              hidden_sizes=(32,), ghost_batch_size=8)
    lb = LargeBatchConfig(batch_size=16, base_batch_size=16,
                          ghost_batch_size=8)
    regime = Regime(base_lr=0.1, total_steps=4, drop_every=4)
    init_fn, apply_fn = model_fns(cfg)
    params, bn = init_fn(jax.random.PRNGKey(0), cfg)
    fn = make_vision_train_step(apply_fn, cfg, lb, regime)
    args = (params, bn, sgd.init(params),
            jnp.zeros((16, 8, 8, 1), jnp.float32),
            jnp.zeros((16,), jnp.int32), jnp.int32(0),
            jax.random.PRNGKey(1))
    return Built(fn, args, donate_argnums=(0, 1, 2))


def _lm_cfg():
    from repro.configs.registry import get_config
    return dataclasses.replace(get_config("qwen3-1.7b").reduced(),
                               dtype="float32")


def _lm_train_step() -> Built:
    from repro.core import LargeBatchConfig, Regime
    from repro.models import transformer as T
    from repro.optim import sgd
    from repro.train.trainer import make_lm_train_step

    cfg = _lm_cfg()
    lb = LargeBatchConfig(batch_size=2, base_batch_size=2,
                          ghost_batch_size=2)
    regime = Regime(base_lr=0.05, total_steps=4, drop_every=4)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.zeros((2, 32), jnp.int32)}
    fn = make_lm_train_step(cfg, lb, regime)
    args = (params, sgd.init(params), batch, jnp.int32(0),
            jax.random.PRNGKey(1))
    return Built(fn, args, donate_argnums=(0, 1))


def _decode_step() -> Built:
    from repro.models import transformer as T
    from repro.serving.engine import make_serve_step

    cfg = _lm_cfg()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    cache = T.init_cache(cfg, 2, 64, dtype=jnp.float32)
    fn = make_serve_step(cfg)
    args = (params, cache, jnp.zeros((2, 1), jnp.int32), jnp.int32(5))
    return Built(fn, args, donate_argnums=(1,))


def _prefill_fused() -> Built:
    from repro.models import transformer as T
    from repro.serving.engine import prefill_fused

    cfg = _lm_cfg()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    cache = T.init_cache(cfg, 2, 64, dtype=jnp.float32)

    def fn(params, cache, prompts):
        return prefill_fused(params, cfg, prompts, cache)

    args = (params, cache, jnp.zeros((2, 16), jnp.int32))
    return Built(fn, args, donate_argnums=(1,))


def _flash_decode_paged() -> Built:
    from repro.kernels import ops

    B, H, KV, hd = 2, 4, 2, 64
    page, n_pages, n_blocks = 16, 9, 4

    def fn(q, kp, vp, pt, pos):
        return ops.flash_decode_paged(q, kp, vp, pt, pos)

    args = (jnp.zeros((B, 1, H, hd), jnp.float32),
            jnp.zeros((n_pages, KV, page, hd), jnp.float32),
            jnp.zeros((n_pages, KV, page, hd), jnp.float32),
            jnp.zeros((B, n_blocks), jnp.int32),
            jnp.full((B,), 17, jnp.int32))
    return Built(fn, args)


def _flash_decode_paged_int8() -> Built:
    from repro.kernels import ops

    B, H, KV, hd = 2, 4, 2, 64
    page, n_pages, n_blocks = 16, 9, 4

    def fn(q, kp, vp, pt, pos, ks, vs):
        return ops.flash_decode_paged(q, kp, vp, pt, pos, k_scale=ks,
                                      v_scale=vs, rope_theta=1e4)

    args = (jnp.zeros((B, 1, H, hd), jnp.float32),
            jnp.zeros((n_pages, KV, page, hd), jnp.int8),
            jnp.zeros((n_pages, KV, page, hd), jnp.int8),
            jnp.zeros((B, n_blocks), jnp.int32),
            jnp.full((B,), 17, jnp.int32),
            jnp.ones((n_pages, KV, page), jnp.float32),
            jnp.ones((n_pages, KV, page), jnp.float32))
    return Built(fn, args)


def _decode_step_kernels() -> Built:
    from repro.models import transformer as T
    from repro.serving.engine import make_serve_step

    cfg = _lm_cfg()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    cache = T.init_cache(cfg, 2, 64, dtype=jnp.float32, layout="head")
    fn = make_serve_step(cfg, use_kernels=True)
    args = (params, cache, jnp.zeros((2, 1), jnp.int32), jnp.int32(5))
    return Built(fn, args, donate_argnums=(1,))


ENTRIES: List[Entry] = [
    Entry("vision_train_step", "src/repro/train/trainer.py",
          _vision_train_step,
          static_knobs={"use_kernels": 2, "use_gbn": 2}),
    Entry("lm_train_step", "src/repro/train/trainer.py", _lm_train_step,
          static_knobs={"use_kernels": 2, "remat": 2, "seq_parallel": 2}),
    Entry("decode_step", "src/repro/serving/engine.py", _decode_step,
          static_knobs={"use_kernels": 2, "sampling": 2, "ragged": 2}),
    Entry("prefill_fused", "src/repro/serving/engine.py", _prefill_fused,
          static_knobs={"use_kernels": 2, "ragged": 2}),
    # Pallas kernel: jaxpr-only — the TPU kernel path is not XLA-compiled
    # on this backend, and the kernel takes no donated state.
    Entry("flash_decode_paged", "src/repro/kernels/ops.py",
          _flash_decode_paged, compile_check=False,
          static_knobs={"window": 2, "ragged": 2}),
    Entry("flash_decode_paged_int8", "src/repro/kernels/ops.py",
          _flash_decode_paged_int8, compile_check=False,
          static_knobs={"window": 2, "ragged": 2, "rope": 2}),
    # decode_step with the fused-kernel stack (fused RoPE q rotation,
    # rmsnorm+residual, SwiGLU) over a head-major cache. The sampling /
    # ragged axes are shared with the base decode_step entry; cache_dtype
    # covers the int8-paged serving variant.
    Entry("decode_step_kernels", "src/repro/serving/engine.py",
          _decode_step_kernels,
          static_knobs={"sampling": 2, "ragged": 2, "cache_dtype": 2}),
]


def run_trace_audit(entries: Optional[Sequence[Entry]] = None,
                    *, names: Optional[Sequence[str]] = None
                    ) -> List[Finding]:
    """Run every audit for every (selected) registry entry."""
    out: List[Finding] = []
    for entry in entries if entries is not None else ENTRIES:
        if names and entry.name not in names:
            continue
        out.extend(audit_variants(entry))
        b = entry.build()
        out.extend(audit_jaxpr(b.fn, b.args, name=entry.name,
                               path=entry.path))
        if entry.compile_check and b.donate_argnums:
            out.extend(audit_donation(b.fn, b.args, b.donate_argnums,
                                      name=entry.name, path=entry.path))
    return out
