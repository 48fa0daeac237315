"""Continuous-batching serving: paged flash-decode kernels vs oracle and
vs the contiguous cache, per-row decode positions, and the
ContinuousEngine's core guarantee — every request's tokens are bit-exact
vs running that request alone greedily, through EOS retirement, slot
reuse, and mid-flight admission."""
import dataclasses
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.kernels import ref
from repro.kernels.flash_decode import (flash_decode_blockwise,
                                        flash_decode_paged_blockwise,
                                        flash_decode_paged_pallas,
                                        flash_decode_pallas)
from repro.models import transformer as T
from repro.serving import ContinuousEngine, Request, generate


def _cfg(arch, **overrides):
    cfg = dataclasses.replace(get_config(arch).reduced(), dtype="float32")
    if cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=16.0))
    return dataclasses.replace(cfg, **overrides)


def _paged_from_contiguous(k, v, ps, seed=0):
    """Scatter a contiguous (B, KV, S, hd) cache into a page pool with a
    shuffled block table (page 0 reserved as the trash page)."""
    B, KV, S, hd = k.shape
    NB = S // ps
    perm = np.random.RandomState(seed).permutation(
        np.arange(1, 1 + B * NB)).astype(np.int32)
    pt = jnp.asarray(perm.reshape(B, NB))
    def pool(x):
        blocks = x.reshape(B, KV, NB, ps, hd).transpose(0, 2, 1, 3, 4)
        p = jnp.zeros((1 + B * NB, KV, ps, hd), x.dtype)
        return p.at[pt.reshape(-1)].set(blocks.reshape(B * NB, KV, ps, hd))
    return pool(k), pool(v), pt


# ---------------------------------------------------------------------------
# paged flash-decode kernels vs oracle / vs contiguous
# ---------------------------------------------------------------------------


@pytest.mark.tier1
@pytest.mark.parametrize("B,H,KV,NB,ps,hd,window,offs", [
    (2, 4, 4, 4, 16, 64, None, None),        # MHA causal
    (2, 4, 2, 4, 16, 64, None, None),        # GQA
    (2, 8, 2, 4, 16, 64, 24, None),          # window mask over pages
    (3, 4, 1, 2, 32, 32, None, (0, 5, 40)),  # ragged left padding
])
def test_flash_decode_paged_vs_contiguous(B, H, KV, NB, ps, hd, window,
                                          offs):
    """Paged kernel (shuffled block table) == contiguous oracle at per-row
    positions, for pallas-interpret, blockwise, and the paged ref."""
    S = NB * ps
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, KV, S, hd))
    v = jax.random.normal(ks[2], (B, KV, S, hd))
    off = None if offs is None else jnp.array(offs, jnp.int32)
    lo = 0 if offs is None else max(offs)
    # per-row positions at different depths (incl. one mid-page)
    pos = jnp.asarray([max(lo, S - 1 - 7 * i) for i in range(B)], jnp.int32)
    kp, vp, pt = _paged_from_contiguous(k, v, ps)
    o_ref = ref.flash_decode_ref(q, k, v, pos, window=window, offsets=off)
    for name, o in [
        ("paged_ref", ref.flash_decode_paged_ref(
            q, kp, vp, pt, pos, window=window, offsets=off)),
        ("pallas", flash_decode_paged_pallas(
            q, kp, vp, pt, pos, window=window, offsets=off,
            interpret=True)),
        ("blockwise", flash_decode_paged_blockwise(
            q, kp, vp, pt, pos, window=window, offsets=off)),
    ]:
        np.testing.assert_allclose(o, o_ref, atol=3e-6, rtol=1e-5,
                                   err_msg=name)


@pytest.mark.tier1
def test_flash_decode_paged_trash_page_is_noop():
    """Table entries for blocks beyond pos may point at the trash page 0:
    their slots are fully masked, which must be an exact no-op under the
    online softmax. An all-trash row still yields finite output."""
    B, H, KV, NB, ps, hd = 2, 4, 2, 4, 16, 64
    S = NB * ps
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, KV, S, hd))
    v = jax.random.normal(ks[2], (B, KV, S, hd))
    pos = jnp.asarray([ps + 3, 2 * ps - 1], jnp.int32)   # rows use 2 blocks
    kp, vp, pt = _paged_from_contiguous(k, v, ps)
    full = flash_decode_paged_pallas(q, kp, vp, pt, pos, interpret=True)
    trashed = pt.at[:, 2:].set(0)                        # unbacked tail
    for fn in (lambda *a: flash_decode_paged_pallas(*a, interpret=True),
               flash_decode_paged_blockwise):
        got = fn(q, kp, vp, trashed, pos)
        np.testing.assert_allclose(got, full, atol=3e-6, rtol=1e-5)
        dead = fn(q, kp, vp, jnp.zeros_like(pt), pos)    # retired rows
        assert np.isfinite(np.asarray(dead)).all()


@pytest.mark.tier1
@pytest.mark.parametrize("ring", [False, True])
def test_flash_decode_per_row_pos_matches_scalar(ring):
    """A (B,) pos vector == B independent scalar-pos calls, for the
    contiguous pallas kernel and its blockwise serving lowering."""
    B, H, KV, S, hd = 3, 4, 2, 64, 32
    window = S if ring else None
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, KV, S, hd))
    v = jax.random.normal(ks[2], (B, KV, S, hd))
    pos = jnp.asarray([5, S // 2, S + 9 if ring else S - 1], jnp.int32)
    for fn in (lambda *a, **kw: flash_decode_pallas(*a, interpret=True,
                                                    **kw),
               flash_decode_blockwise):
        vec = fn(q, k, v, pos, window=window, ring=ring)
        for b in range(B):
            one = fn(q[b:b + 1], k[b:b + 1], v[b:b + 1],
                     jnp.int32(int(pos[b])), window=window, ring=ring)
            np.testing.assert_allclose(vec[b:b + 1], one, atol=3e-6,
                                       rtol=1e-5)


@pytest.mark.tier1
@pytest.mark.parametrize("use_kernels", [False, True])
def test_decode_step_vector_pos_matches_scalar(use_kernels):
    """Model-level: decode_step with pos as a (B,) vector (all rows equal)
    is bit-identical to the scalar-pos training/generate path."""
    cfg = _cfg("qwen3-1.7b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    layout = "head" if use_kernels else "seq"
    B, S, p = 2, 16, 7
    tok = jax.random.randint(jax.random.PRNGKey(1), (B, 1), 0,
                             cfg.vocab_size)
    mk = lambda: T.init_cache(cfg, B, S, dtype=jnp.float32, layout=layout)
    l_s, c_s = T.decode_step(params, cfg, tok, mk(), jnp.int32(p),
                             use_kernels=use_kernels)
    l_v, c_v = T.decode_step(params, cfg, tok, mk(),
                             jnp.full((B,), p, jnp.int32),
                             use_kernels=use_kernels)
    np.testing.assert_array_equal(np.asarray(l_s), np.asarray(l_v))
    for (ps_, a), (pv, b) in zip(jax.tree_util.tree_leaves_with_path(c_s),
                                 jax.tree_util.tree_leaves_with_path(c_v)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(ps_))


# ---------------------------------------------------------------------------
# ContinuousEngine vs solo generate
# ---------------------------------------------------------------------------


def _trace(cfg, n, seed=0):
    """Staggered arrivals, 2 prompt lengths, one budget — bounds the
    distinct compile shapes while still forcing mid-flight admission."""
    r = np.random.RandomState(seed)
    out = []
    for i in range(n):
        L = int(r.choice([4, 8]))
        prompt = r.randint(0, cfg.vocab_size, size=(L,)).astype("int32")
        out.append(Request(id=i, prompt=prompt, max_new_tokens=6,
                           arrival=0.9 * i))
    return out


def _solo(params, cfg, req, max_len, uk):
    prompt = jnp.asarray(req.prompt, jnp.int32)
    out = generate(params, cfg, prompt[None],
                   max_new_tokens=req.max_new_tokens, max_len=max_len,
                   use_kernels=uk)
    return np.asarray(out[0, prompt.shape[0]:])


@pytest.mark.tier1
@pytest.mark.parametrize("arch,use_kernels", [
    ("qwen3-1.7b", False),        # GQA full attention, einsum decode
    ("qwen3-1.7b", True),         # paged flash-decode kernel path
    ("h2o-danube-3-4b", False),   # all-SWA: ring fallback under "paged"
    ("falcon-mamba-7b", False),   # SSM state rows ride the slot scatter
])
def test_continuous_engine_matches_solo(arch, use_kernels):
    """Every completion == running that request alone greedily: per-row
    pos, paged gather, admission scatter, and retirement must all be
    invisible to the numerics. 5 requests through 2 slots forces slot
    reuse and mid-flight admission."""
    cfg = _cfg(arch)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = _trace(cfg, 5)
    eng = ContinuousEngine(params, cfg, num_slots=2, max_len=16,
                           layout="paged", page_size=8,
                           use_kernels=use_kernels)
    comps = eng.run(reqs)
    assert sorted(comps) == [r.id for r in reqs]
    for r in reqs:
        want = _solo(params, cfg, r, 16, use_kernels)
        np.testing.assert_array_equal(
            np.asarray(comps[r.id].tokens), want,
            err_msg=f"request {r.id} (L={len(r.prompt)})")


@pytest.mark.tier1
def test_eos_retirement_and_slot_reuse():
    """A row that emits eos_id retires early (tokens end at the first
    EOS), its slot is re-admitted mid-flight, and the newcomer in the
    recycled slot is still bit-exact vs a fresh solo run."""
    cfg = _cfg("qwen3-1.7b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = _trace(cfg, 4, seed=3)
    solo = {r.id: _solo(params, cfg, r, 16, False) for r in reqs}
    eos = int(solo[0][2])             # force req 0 to EOS mid-stream
    eng = ContinuousEngine(params, cfg, num_slots=2, max_len=16,
                           layout="paged", page_size=8, eos_id=eos)
    comps = eng.run(reqs)
    retired_early = False
    for r in reqs:
        want = list(solo[r.id])
        if eos in want:               # truncate at first EOS, inclusive
            want = want[:want.index(eos) + 1]
            retired_early = retired_early or len(want) < r.max_new_tokens
        np.testing.assert_array_equal(np.asarray(comps[r.id].tokens),
                                      np.asarray(want),
                                      err_msg=f"request {r.id}")
    assert retired_early              # the EOS path actually fired
    assert not eng.active.any() and not eng.free_pages == []


@pytest.mark.tier1
def test_paged_engine_matches_contiguous_engine():
    """layout='paged' vs the contiguous layouts: same trace, identical
    completions — the block-table indirection is numerically invisible."""
    cfg = _cfg("qwen3-1.7b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = _trace(cfg, 4, seed=5)
    outs = {}
    for layout in ("paged", "seq", "head"):
        eng = ContinuousEngine(params, cfg, num_slots=2, max_len=16,
                               layout=layout, page_size=8)
        outs[layout] = {i: c.tokens for i, c in eng.run(reqs).items()}
    assert outs["paged"] == outs["seq"] == outs["head"]


def test_engine_validation():
    cfg = _cfg("qwen3-1.7b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="multiple of"):
        ContinuousEngine(params, cfg, num_slots=2, max_len=20,
                         layout="paged", page_size=8)
    with pytest.raises(ValueError, match="cannot hold"):
        ContinuousEngine(params, cfg, num_slots=2, max_len=16,
                         layout="paged", page_size=8, total_pages=2)
    eng = ContinuousEngine(params, cfg, num_slots=2, max_len=16,
                           layout="paged", page_size=8)
    long = np.zeros((14,), np.int32)
    with pytest.raises(ValueError, match="does not fit"):
        eng.run([Request(id=0, prompt=long, max_new_tokens=8)])
    with pytest.raises(ValueError, match="does not fit"):
        eng.run([Request(id=0, prompt=long[:4], max_new_tokens=0)])


# ---------------------------------------------------------------------------
# model-sharded serving (subprocess: 4 devices as a (2 data, 2 model) mesh)
# ---------------------------------------------------------------------------


SHARDED_ENGINE_SCRIPT = textwrap.dedent("""
    import dataclasses
    import jax, numpy as np
    assert jax.device_count() == 4, jax.device_count()
    from repro.configs.registry import get_config
    from repro.launch.mesh import MODEL_AXIS, make_2d_mesh
    from repro.models import transformer as T
    from repro.serving import ContinuousEngine, Request

    cfg = dataclasses.replace(get_config("qwen3-1.7b").reduced(),
                              dtype="float32")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    r = np.random.RandomState(0)
    reqs = []
    for i in range(5):
        L = int(r.choice([4, 8]))
        prompt = r.randint(0, cfg.vocab_size, size=(L,)).astype("int32")
        reqs.append(Request(id=i, prompt=prompt, max_new_tokens=6,
                            arrival=0.9 * i))

    kw = dict(num_slots=2, max_len=16, layout="paged", page_size=8)
    solo = ContinuousEngine(params, cfg, **kw).run(reqs)

    mesh = make_2d_mesh()
    eng = ContinuousEngine(params, cfg, mesh=mesh, **kw)
    # the page pool really is sharded over kv heads per rules.cache_specs
    kp = eng.cache["body"][0]["attn"]["kp"]
    spec = tuple(kp.sharding.spec)
    assert MODEL_AXIS in spec, spec
    sharded = eng.run(reqs)
    assert sorted(sharded) == sorted(solo)
    for i in solo:
        assert sharded[i].tokens == solo[i].tokens, (
            i, sharded[i].tokens, solo[i].tokens)
    print("SERVING_SHARDED_OK")
""")


@pytest.mark.tier1
def test_sharded_engine_matches_unsharded_subprocess():
    """ContinuousEngine on the (2 data, 2 model) serving mesh — params per
    rules.param_specs, paged KV pool sharded over kv heads per
    rules.cache_specs — emits greedy tokens bit-exact vs the unsharded
    engine on the same trace."""
    import os
    import subprocess
    import sys
    from pathlib import Path
    repo = Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(repo / "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env["JAX_PLATFORMS"] = "cpu"   # the child must never reach for a chip
    proc = subprocess.run([sys.executable, "-c", SHARDED_ENGINE_SCRIPT],
                          capture_output=True, text=True, env=env,
                          cwd=str(repo), timeout=900)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "SERVING_SHARDED_OK" in proc.stdout
