"""Per-kernel validation: sweep shapes/dtypes, assert_allclose against the
pure-jnp oracles in kernels/ref.py (kernels run in interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

pytestmark = pytest.mark.tier1

# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

ATTN_SHAPES = [
    # (B, H, KV, T, S, hd)
    (1, 2, 2, 17, 17, 32),
    (2, 4, 2, 64, 64, 64),
    (1, 8, 1, 128, 128, 64),     # MQA
    (2, 4, 4, 100, 100, 128),    # MHA, ragged T
]


@pytest.mark.parametrize("shape", ATTN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 13),
                                           (False, None)])
def test_flash_attention_vs_ref(shape, dtype, causal, window):
    B, H, KV, T, S, hd = shape
    rng = jax.random.PRNGKey(hash((shape, causal, window or 0)) % 2**31)
    q = jax.random.normal(rng, (B, H, T, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, KV, S, hd),
                          jnp.float32).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(rng, 2), (B, KV, S, hd),
                          jnp.float32).astype(dtype)
    out = ops.flash_attention_hm(q, k, v, causal=causal, window=window,
                                 block_q=32, block_k=32)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_unaligned_default_blocks():
    """T/S not a multiple of 8 with the DEFAULT block sizes: the picked
    blocks must be sublane-aligned (T=100 -> bq=104, not 100) and the
    padded result must still match the oracle."""
    from repro.kernels.flash_attention import _block_sizes
    bq, bk = _block_sizes(100, 100, 128, 128, jnp.float32)
    assert bq % 8 == 0 and bk % 8 == 0, (bq, bk)
    bq16, bk16 = _block_sizes(100, 100, 128, 128, jnp.bfloat16)
    assert bq16 % 16 == 0 and bk16 % 16 == 0, (bq16, bk16)
    rng = jax.random.PRNGKey(2)
    q = jax.random.normal(rng, (1, 4, 100, 32))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (1, 2, 100, 32))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (1, 2, 100, 32))
    out = ops.flash_attention_hm(q, k, v, causal=True)   # default blocks
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


def test_flash_attention_model_layout():
    """(B, T, H, hd) adapter used by the model code."""
    rng = jax.random.PRNGKey(0)
    q = jax.random.normal(rng, (2, 32, 4, 64))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (2, 32, 2, 64))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (2, 32, 2, 64))
    out = ops.flash_attention(q, k, v, causal=True)
    want = ref.attention_ref(q.swapaxes(1, 2), k.swapaxes(1, 2),
                             v.swapaxes(1, 2), causal=True).swapaxes(1, 2)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash attention — gradients (Pallas backward via custom_vjp)
# ---------------------------------------------------------------------------

ATTN_GRAD_SHAPES = [
    # (B, H, KV, T, S, hd)
    (1, 2, 2, 17, 17, 32),       # ragged (non-multiple-of-8 T/S)
    (2, 4, 2, 64, 64, 32),       # GQA
    (1, 4, 1, 64, 64, 32),       # MQA
]


def _attn_inputs(shape, dtype, salt=0):
    B, H, KV, T, S, hd = shape
    rng = jax.random.PRNGKey((sum(shape) + salt) % 2**31)
    q = jax.random.normal(rng, (B, H, T, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, KV, S, hd),
                          jnp.float32).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(rng, 2), (B, KV, S, hd),
                          jnp.float32).astype(dtype)
    w = jax.random.normal(jax.random.fold_in(rng, 3), (B, H, T, hd))
    return q, k, v, w


@pytest.mark.parametrize("shape", ATTN_GRAD_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 13),
                                           (False, None)])
def test_flash_attention_grad_vs_ref(shape, dtype, causal, window):
    """jax.grad through the kernel custom_vjp == jax.grad through the
    oracle, across causal/window/GQA/ragged shapes in f32 and bf16."""
    q, k, v, w = _attn_inputs(shape, dtype)

    def make_loss(f):
        return lambda a, b, c: (
            f(a, b, c).astype(jnp.float32) * w).sum()

    gk = jax.grad(make_loss(lambda a, b, c: ops.flash_attention_hm(
        a, b, c, causal=causal, window=window, block_q=32, block_k=32)),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(make_loss(lambda a, b, c: ref.attention_ref(
        a, b, c, causal=causal, window=window)), argnums=(0, 1, 2))(q, k, v)
    tol = 5e-4 if dtype == jnp.float32 else 1e-1
    for got, want in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)


@pytest.mark.tier0
def test_flash_attention_grad_smoke():
    """Seconds-scale quick-gate case: causal f32 grad vs oracle."""
    q, k, v, w = _attn_inputs((1, 2, 1, 16, 16, 16), jnp.float32)

    def make_loss(f):
        return lambda a, b, c: (f(a, b, c) * w).sum()

    gk = jax.grad(make_loss(lambda a, b, c: ops.flash_attention_hm(
        a, b, c, block_q=16, block_k=16)), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(make_loss(ref.attention_ref), argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(gk, gr):
        np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("causal,window", [(True, None), (True, 13),
                                           (False, None)])
def test_attention_vjp_ref_matches_autodiff(causal, window):
    """The hand-derived oracle VJP == jax.vjp of the jnp oracle
    (GQA + ragged shape)."""
    q, k, v, do = _attn_inputs((2, 4, 2, 37, 37, 16), jnp.float32, salt=3)
    _, vjp = jax.vjp(lambda *a: ref.attention_ref(
        *a, causal=causal, window=window), q, k, v)
    want = vjp(do)
    got = ref.attention_vjp_ref(q, k, v, do, causal=causal, window=window)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", ATTN_GRAD_SHAPES)
@pytest.mark.parametrize("causal,window", [(True, None), (True, 13),
                                           (False, None)])
def test_flash_backward_kernel_vs_hand_vjp(shape, causal, window):
    """flash_attention_backward_pallas directly against the hand oracle,
    fed the forward kernel's own (o, lse) residuals."""
    from repro.kernels.flash_attention import (
        flash_attention_backward_pallas, flash_attention_pallas)
    q, k, v, do = _attn_inputs(shape, jnp.float32, salt=7)
    o, lse = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                    block_q=32, block_k=32,
                                    return_residuals=True, interpret=True)
    dq, dk, dv = flash_attention_backward_pallas(
        q, k, v, o, lse, do, causal=causal, window=window, block_q=32,
        block_k=32, interpret=True)
    dqr, dkr, dvr = ref.attention_vjp_ref(q, k, v, do, causal=causal,
                                          window=window)
    np.testing.assert_allclose(dq, dqr, rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(dk, dkr, rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(dv, dvr, rtol=5e-4, atol=5e-4)


# ---------------------------------------------------------------------------
# ghost batch norm kernel
# ---------------------------------------------------------------------------

# (2, 512, 16) and (2, 200, 64) take the lane fold of ResNet44's narrow
# channel axes (the second with a row count that needs padding after it)
GBN_SHAPES = [(1, 16, 8), (4, 300, 96), (2, 1024, 128), (3, 77, 200),
              (2, 512, 16), (2, 200, 64)]


@pytest.mark.parametrize("shape", GBN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gbn_kernel_vs_ref(shape, dtype):
    G, R, C = shape
    rng = jax.random.PRNGKey(G * 1000 + R)
    xg = (2.0 * jax.random.normal(rng, shape, jnp.float32) + 0.5).astype(dtype)
    gamma = jnp.linspace(0.5, 1.5, C)
    beta = jnp.linspace(-1.0, 1.0, C)
    y, mu, var = ops.gbn_forward(xg, gamma, beta)
    yr, mur, varr = ref.gbn_ref(xg, gamma, beta)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(mu), np.asarray(mur),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(var), np.asarray(varr),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               rtol=10 * tol, atol=10 * tol)


def test_gbn_kernel_inside_module():
    """core.gbn_apply(use_kernels=True) matches the jnp path."""
    from repro.core.gbn import gbn_apply, gbn_init
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 24)) * 2 + 1
    params, state = gbn_init(24)
    y0, s0 = gbn_apply(params, state, x, ghost_batch_size=16)
    y1, s1 = gbn_apply(params, state, x, ghost_batch_size=16,
                       use_kernels=True)
    np.testing.assert_allclose(y0, y1, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s0["mu_run"], s1["mu_run"], rtol=1e-4,
                               atol=1e-4)


def test_gbn_kernel_leftover_rows():
    """B not divisible by the ghost size: the tail is normalized with the
    last ghost's stats; kernel and jnp paths must agree (fwd AND grad)."""
    from repro.core.gbn import gbn_apply, gbn_init
    x = jax.random.normal(jax.random.PRNGKey(3), (70, 24)) * 2 + 1
    params, state = gbn_init(24)
    y0, s0 = gbn_apply(params, state, x, ghost_batch_size=16)
    y1, s1 = gbn_apply(params, state, x, ghost_batch_size=16,
                       use_kernels=True)
    np.testing.assert_allclose(y0, y1, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s0["mu_run"], s1["mu_run"], rtol=1e-4,
                               atol=1e-4)
    # the tail path makes the mu/var outputs of the kernel gradient-carrying
    w = jax.random.normal(jax.random.PRNGKey(4), (70, 24))

    def loss(p, uk):
        y, _ = gbn_apply(p, state, x, ghost_batch_size=16, use_kernels=uk)
        return (y * w).sum()

    g0 = jax.grad(lambda p: loss(p, False))(params)
    g1 = jax.grad(lambda p: loss(p, True))(params)
    for k in g0:
        np.testing.assert_allclose(g0[k], g1[k], rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# ghost batch norm kernel — gradients (Pallas backward via custom_vjp)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", GBN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gbn_grad_vs_ref(shape, dtype):
    """jax.grad through the kernel custom_vjp == jax.grad through the
    oracle, with live cotangents on ALL THREE outputs (y, mu, var)."""
    G, R, C = shape
    rng = jax.random.PRNGKey(G * 777 + R)
    xg = (2.0 * jax.random.normal(rng, shape, jnp.float32) + 0.5).astype(dtype)
    gamma = jnp.linspace(0.5, 1.5, C)
    beta = jnp.linspace(-1.0, 1.0, C)
    wy = jax.random.normal(jax.random.fold_in(rng, 1), shape)
    wm = jax.random.normal(jax.random.fold_in(rng, 2), (G, C))
    wv = jax.random.normal(jax.random.fold_in(rng, 3), (G, C))

    def make_loss(f):
        def loss(x, g, b):
            y, mu, var = f(x, g, b)
            return ((y.astype(jnp.float32) * wy).sum()
                    + (mu * wm).sum() + (var * wv).sum())
        return loss

    gk = jax.grad(make_loss(ops.gbn_forward), argnums=(0, 1, 2))(
        xg, gamma, beta)
    gr = jax.grad(make_loss(ref.gbn_ref), argnums=(0, 1, 2))(xg, gamma, beta)
    tol = 1e-4 if dtype == jnp.float32 else 1e-1
    for got, want in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)


@pytest.mark.parametrize("shape", GBN_SHAPES)
def test_gbn_backward_kernel_vs_hand_vjp(shape):
    """gbn_backward_pallas directly against the hand-derived oracle VJP."""
    from repro.kernels.gbn import gbn_backward_pallas
    G, R, C = shape
    rng = jax.random.PRNGKey(G + R + C)
    xg = 2.0 * jax.random.normal(rng, shape) + 0.5
    gamma = jnp.linspace(0.5, 1.5, C)
    beta = jnp.zeros((C,))
    dy = jax.random.normal(jax.random.fold_in(rng, 1), shape)
    dmu = jax.random.normal(jax.random.fold_in(rng, 2), (G, C))
    dvar = jax.random.normal(jax.random.fold_in(rng, 3), (G, C))
    _, mu, var = ref.gbn_ref(xg, gamma, beta)
    dx, dgamma, dbeta = gbn_backward_pallas(xg, gamma, mu, var, dy, dmu,
                                            dvar, interpret=True)
    dxr, dgr, dbr = ref.gbn_vjp_ref(xg, gamma, beta, (dy, dmu, dvar))
    np.testing.assert_allclose(dx, dxr, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(dgamma, dgr, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(dbeta, dbr, rtol=1e-4, atol=1e-4)


def test_gbn_vjp_ref_matches_autodiff():
    """The hand-derived oracle VJP == jax.vjp of the jnp oracle."""
    G, R, C = 3, 50, 17
    rng = jax.random.PRNGKey(5)
    xg = jax.random.normal(rng, (G, R, C)) * 3 - 1
    gamma = jnp.linspace(0.2, 2.0, C)
    beta = jnp.linspace(-0.5, 0.5, C)
    cts = (jax.random.normal(jax.random.fold_in(rng, 1), (G, R, C)),
           jax.random.normal(jax.random.fold_in(rng, 2), (G, C)),
           jax.random.normal(jax.random.fold_in(rng, 3), (G, C)))
    _, vjp = jax.vjp(lambda *a: ref.gbn_ref(*a), xg, gamma, beta)
    want = vjp(cts)
    got = ref.gbn_vjp_ref(xg, gamma, beta, cts)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_vision_train_step_kernel_path_matches():
    """A full make_vision_train_step(use_kernels=True) step runs under grad
    and matches the non-kernel step's loss and updated params."""
    import dataclasses
    from repro.configs.paper_models import F1_MNIST
    from repro.core import LargeBatchConfig, Regime
    from repro.models.cnn import model_fns
    from repro.optim import sgd
    from repro.train.trainer import make_vision_train_step
    cfg = dataclasses.replace(F1_MNIST, input_shape=(8, 8, 1),
                              hidden_sizes=(32,), ghost_batch_size=16)
    lb = LargeBatchConfig(batch_size=64, base_batch_size=64,
                          ghost_batch_size=16)
    regime = Regime(base_lr=0.1, total_steps=10, drop_every=10)
    init_fn, apply_fn = model_fns(cfg)
    params, bn = init_fn(jax.random.PRNGKey(0), cfg)
    opt = sgd.init(params)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 8, 8, 1))
    y = jax.random.randint(jax.random.PRNGKey(2), (64,), 0, 10)
    outs = {}
    for uk in (False, True):
        step = jax.jit(make_vision_train_step(apply_fn, cfg, lb, regime,
                                              use_kernels=uk))
        outs[uk] = step(params, bn, opt, x, y, jnp.int32(0),
                        jax.random.PRNGKey(3))
    p0, _, _, m0 = outs[False]
    p1, _, _, m1 = outs[True]
    np.testing.assert_allclose(float(m0["loss"]), float(m1["loss"]),
                               rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p1)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# mamba chunk scan kernel
# ---------------------------------------------------------------------------

MAMBA_SHAPES = [
    # (B, c, di, ds)
    (1, 8, 128, 8),
    (2, 16, 256, 16),
    (2, 32, 512, 16),
]


@pytest.mark.parametrize("shape", MAMBA_SHAPES)
def test_mamba_chunk_vs_ref(shape):
    B, c, di, ds = shape
    rng = jax.random.PRNGKey(sum(shape))
    xc = jax.random.normal(rng, (B, c, di))
    dt = 0.1 * jax.nn.softplus(
        jax.random.normal(jax.random.fold_in(rng, 1), (B, c, di)))
    Bm = jax.random.normal(jax.random.fold_in(rng, 2), (B, c, ds))
    Cm = jax.random.normal(jax.random.fold_in(rng, 3), (B, c, ds))
    A = -jnp.abs(jax.random.normal(jax.random.fold_in(rng, 4), (di, ds)))
    h0 = jax.random.normal(jax.random.fold_in(rng, 5), (B, di, ds))
    y, h = ops.mamba_chunk(xc, dt, Bm, Cm, A, h0)
    yr, hr = ref.mamba_chunk_ref(xc, dt, Bm, Cm, A, h0)
    np.testing.assert_allclose(y, yr, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(h, hr, rtol=1e-4, atol=1e-4)


def test_mamba_chunk_chains_across_chunks():
    """Carrying h across two chunks == one long reference scan."""
    B, c, di, ds = 1, 8, 128, 8
    rng = jax.random.PRNGKey(9)
    xc = jax.random.normal(rng, (B, 2 * c, di))
    dt = 0.1 * jnp.ones((B, 2 * c, di))
    Bm = jax.random.normal(jax.random.fold_in(rng, 1), (B, 2 * c, ds))
    Cm = jax.random.normal(jax.random.fold_in(rng, 2), (B, 2 * c, ds))
    A = -jnp.abs(jax.random.normal(jax.random.fold_in(rng, 3), (di, ds)))
    h0 = jnp.zeros((B, di, ds))
    y1, h1 = ops.mamba_chunk(xc[:, :c], dt[:, :c], Bm[:, :c], Cm[:, :c], A, h0)
    y2, h2 = ops.mamba_chunk(xc[:, c:], dt[:, c:], Bm[:, c:], Cm[:, c:], A, h1)
    yr, hr = ref.mamba_chunk_ref(xc, dt, Bm, Cm, A, h0)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], axis=1), yr,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(h2, hr, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# mamba chunk scan kernel — gradients (Pallas backward via custom_vjp)
# ---------------------------------------------------------------------------


def _mamba_inputs(B, c, di, ds, key=0, dtype=jnp.float32):
    rng = jax.random.PRNGKey(key)
    xc = jax.random.normal(rng, (B, c, di)).astype(dtype)
    dt = (0.1 * jax.nn.softplus(jax.random.normal(
        jax.random.fold_in(rng, 1), (B, c, di)))).astype(dtype)
    Bm = jax.random.normal(jax.random.fold_in(rng, 2), (B, c, ds)).astype(dtype)
    Cm = jax.random.normal(jax.random.fold_in(rng, 3), (B, c, ds)).astype(dtype)
    A = -jnp.abs(jax.random.normal(jax.random.fold_in(rng, 4), (di, ds)))
    h0 = jax.random.normal(jax.random.fold_in(rng, 5), (B, di, ds))
    return xc, dt, Bm, Cm, A, h0


@pytest.mark.parametrize("shape", MAMBA_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mamba_grad_vs_ref(shape, dtype):
    """jax.grad through the kernel custom_vjp == jax.grad through the
    oracle, with live cotangents on BOTH outputs (y and h_last) and a
    nonzero h0."""
    B, c, di, ds = shape
    args = _mamba_inputs(B, c, di, ds, key=sum(shape), dtype=dtype)
    rng = jax.random.PRNGKey(sum(shape) + 1)
    wy = jax.random.normal(rng, (B, c, di))
    wh = jax.random.normal(jax.random.fold_in(rng, 1), (B, di, ds))

    def make_loss(f):
        def loss(*a):
            y, h_last = f(*a)
            return (y * wy).sum() + (h_last * wh).sum()
        return loss

    gk = jax.grad(make_loss(ops.mamba_chunk),
                  argnums=tuple(range(6)))(*args)
    gr = jax.grad(make_loss(ref.mamba_chunk_ref),
                  argnums=tuple(range(6)))(*args)
    tol = 2e-4 if dtype == jnp.float32 else 1e-1
    for got, want in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)


@pytest.mark.tier0
def test_mamba_grad_multichunk_smoke():
    """Quick-gate case: grads through TWO chained kernel chunks (nonzero
    carried h) == grads through one long oracle scan."""
    B, c, di, ds = 1, 8, 128, 8
    xc, dt, Bm, Cm, A, h0 = _mamba_inputs(B, 2 * c, di, ds, key=11)
    wy = jax.random.normal(jax.random.PRNGKey(12), (B, 2 * c, di))

    def two_chunk(xc, dt, Bm, Cm, A, h0):
        y1, h1 = ops.mamba_chunk(xc[:, :c], dt[:, :c], Bm[:, :c],
                                 Cm[:, :c], A, h0)
        y2, _ = ops.mamba_chunk(xc[:, c:], dt[:, c:], Bm[:, c:],
                                Cm[:, c:], A, h1)
        return jnp.concatenate([y1, y2], axis=1)

    gk = jax.grad(lambda *a: (two_chunk(*a) * wy).sum(),
                  argnums=tuple(range(6)))(xc, dt, Bm, Cm, A, h0)
    gr = jax.grad(lambda *a: (ref.mamba_chunk_ref(*a)[0] * wy).sum(),
                  argnums=tuple(range(6)))(xc, dt, Bm, Cm, A, h0)
    for got, want in zip(gk, gr):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("shape", MAMBA_SHAPES)
def test_mamba_backward_kernel_vs_oracle_vjp(shape):
    """mamba_chunk_backward_pallas directly against the oracle VJP."""
    from repro.kernels.mamba_scan import mamba_chunk_backward_pallas
    B, c, di, ds = shape
    args = _mamba_inputs(B, c, di, ds, key=sum(shape) + 5)
    rng = jax.random.PRNGKey(sum(shape) + 6)
    dy = jax.random.normal(rng, (B, c, di))
    dhl = jax.random.normal(jax.random.fold_in(rng, 1), (B, di, ds))
    got = mamba_chunk_backward_pallas(*args, dy, dhl, di_tile=128,
                                      interpret=True)
    want = ref.mamba_chunk_vjp_ref(*args, (dy, dhl))
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)


def test_mamba_backward_no_oracle_replay(monkeypatch):
    """The custom-VJP backward must not re-run the oracle forward: poison
    the oracle and check jax.grad through the kernel path still works."""
    def boom(*a, **kw):
        raise AssertionError("oracle forward replayed in backward")

    monkeypatch.setattr(ref, "mamba_chunk_ref", boom)
    monkeypatch.setattr(ops.ref, "mamba_chunk_ref", boom)
    args = _mamba_inputs(1, 8, 128, 8, key=21)
    g = jax.grad(lambda *a: ops.mamba_chunk(*a)[0].sum(),
                 argnums=(0,))(*args)
    assert np.all(np.isfinite(np.asarray(g[0])))


def test_mamba_unaligned_tile_fallback():
    """d_inner without a 128-multiple divisor runs as one untiled
    whole-axis block (with a one-time warning) instead of silently dropping
    to the oracle; past the VMEM bound it still gets the oracle, loudly.
    Both stay correct (fwd and grad)."""
    import warnings as warnings_mod
    assert ops._mamba_tile(100) == 100            # untiled whole axis
    assert ops._mamba_tile(192) == 192
    assert ops._mamba_tile(640) == 128            # 128-multiple: strict tile
    assert ops._mamba_tile(1100) is None          # past the VMEM bound

    ops._TILE_WARNED.clear()
    with warnings_mod.catch_warnings(record=True) as rec:
        warnings_mod.simplefilter("always")
        args = _mamba_inputs(1, 8, 100, 8, key=31)
        y, h = ops.mamba_chunk(*args)
        yr, hr = ref.mamba_chunk_ref(*args)
        np.testing.assert_allclose(y, yr, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(h, hr, rtol=1e-4, atol=1e-4)
        gk = jax.grad(lambda *a: ops.mamba_chunk(*a)[0].sum(),
                      argnums=(0, 4))(*args)
        gr = jax.grad(lambda *a: ref.mamba_chunk_ref(*a)[0].sum(),
                      argnums=(0, 4))(*args)
        for g, w in zip(gk, gr):
            np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)
    assert any("no 128-multiple divisor" in str(w.message) for w in rec)

    ops._TILE_WARNED.clear()
    with warnings_mod.catch_warnings(record=True) as rec:
        warnings_mod.simplefilter("always")
        args = _mamba_inputs(1, 8, 1100, 8, key=32)   # oracle fallback
        y, h = ops.mamba_chunk(*args)
        yr, hr = ref.mamba_chunk_ref(*args)
        np.testing.assert_allclose(y, yr, rtol=1e-4, atol=1e-4)
        # the oracle-fallback custom_vjp branch must also differentiate
        gk = jax.grad(lambda *a: ops.mamba_chunk(*a)[0].sum(),
                      argnums=(0, 4))(*args)
        gr = jax.grad(lambda *a: ref.mamba_chunk_ref(*a)[0].sum(),
                      argnums=(0, 4))(*args)
        for g, w in zip(gk, gr):
            np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)
    assert any("un-tiled jnp oracle" in str(w.message) for w in rec)


# ---------------------------------------------------------------------------
# LM train step through both kernel mixers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "falcon-mamba-7b"])
def test_lm_train_step_kernel_path_matches(arch):
    """A full make_lm_train_step(use_kernels=True) step runs under grad
    through the Pallas attention / Mamba custom-VJPs and matches the
    non-kernel step's loss and updated params."""
    import dataclasses
    from repro.configs.registry import get_config
    from repro.core import LargeBatchConfig, Regime
    from repro.models import transformer as T
    from repro.optim import sgd
    from repro.train.trainer import make_lm_train_step
    cfg = dataclasses.replace(get_config(arch).reduced(), dtype="float32")
    B, S = 2, 32
    lb = LargeBatchConfig(batch_size=B, base_batch_size=B, grad_clip=1.0)
    regime = Regime(base_lr=0.01, total_steps=10, drop_every=10)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    opt = sgd.init(params)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                          cfg.vocab_size)}
    outs = {}
    for uk in (False, True):
        step = jax.jit(make_lm_train_step(cfg, lb, regime, use_kernels=uk))
        outs[uk] = step(params, opt, batch, jnp.int32(0),
                        jax.random.PRNGKey(2))
    np.testing.assert_allclose(float(outs[False][2]["loss"]),
                               float(outs[True][2]["loss"]),
                               rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree.leaves(outs[False][0]),
                    jax.tree.leaves(outs[True][0])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
