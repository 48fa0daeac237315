"""Compile every main-path Pallas kernel for a described TPU v5e, without
the chip.

The TPU compiler is installed with jax: ``topologies.get_topology_desc``
describes a ``v5e:2x2`` host and ``jit(...).lower(shapes).compile()``
raises whatever Mosaic would raise on the chip — misaligned blocks, float
iotas, too much VMEM — none of which interpret mode checks. Shapes are the
widths the chip smoke runs (``chip_smoke.py``): ghost batch norm over each
ResNet44 stage and the F1 MLP, qwen3-1.7b attention / decode / MLP / norm,
and the falcon-mamba-7b chunk scan forward. Each compile takes a second or
two. (The mamba backward is left out: at d_inner 8192 its state scratch,
16 wide and padded to 128 lanes, needs 32 MiB of VMEM; no path the chip
smoke runs uses it.)

The topology is described only inside the module fixture below: loading
the TPU library at import (or in a ``skipif`` / ``parametrize``) would make
pytest-xdist workers collect different tests. The persistent compilation
cache is off around these compiles: an entry written for a described chip
cannot be read back without one.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention import (flash_attention_backward_pallas,
                                           flash_attention_pallas,
                                           flash_attention_rope_pallas)
from repro.kernels.flash_decode import (flash_decode_paged_pallas,
                                        flash_decode_pallas)
from repro.kernels.fused_norm import (rmsnorm_residual_backward_pallas,
                                      rmsnorm_residual_pallas)
from repro.kernels.gbn import gbn_backward_pallas, gbn_forward_pallas
from repro.kernels.mamba_scan import mamba_chunk_pallas
from repro.kernels.swiglu import swiglu_backward_pallas, swiglu_pallas

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
ROPE_THETA = 1e6              # qwen3-1.7b


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler / library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# (name, kernel fn, argument shapes) — every entry must compile to a Mosaic
# custom call
# (ghosts, rows per ghost, channels) at batch 4096, ghost 128: the three
# ResNet44 stages (32x32x16, 16x16x32, 8x8x64) and an F1 hidden layer
GBN_SHAPES = {"resnet44_s1": (32, 128 * 32 * 32, 16),
              "resnet44_s2": (32, 128 * 16 * 16, 32),
              "resnet44_s3": (32, 128 * 8 * 8, 64),
              "f1": (32, 128, 512)}
B_ATT, H, KV, HD = 8, 16, 8, 128            # qwen3-1.7b, 8 rows
T_TRAIN = 2048
N_ROWS, D, FF = 16384, 2048, 6144           # 8 x 2048 tokens, qwen3 MLP
SLOTS, PAGES, PAGE = 8, 64, 16              # engine pool
DI, DS, CHUNK = 8192, 16, 128               # falcon-mamba-7b d_inner, state


def _gbn_bwd(x, g, mu, var, dy, dmu, dvar):
    return gbn_backward_pallas(x, g, mu, var, dy, dmu, dvar)


def _gbn_cases():
    for name, (G, R, C) in GBN_SHAPES.items():
        x, c, gc = ((G, R, C), F32), ((C,), F32), ((G, C), F32)
        yield (f"gbn_fwd_{name}", gbn_forward_pallas, [x, c, c])
        yield (f"gbn_bwd_{name}", _gbn_bwd, [x, c, gc, gc, x, gc, gc])


CASES = list(_gbn_cases()) + [
    ("flash_rope_fwd",
     functools.partial(flash_attention_rope_pallas, theta=ROPE_THETA,
                       return_residuals=True),
     [((B_ATT, H, T_TRAIN, HD), BF16), ((B_ATT, KV, T_TRAIN, HD), BF16),
      ((B_ATT, KV, T_TRAIN, HD), BF16), ((B_ATT, T_TRAIN), F32)]),
    ("flash_bwd", flash_attention_backward_pallas,
     [((B_ATT, H, T_TRAIN, HD), BF16), ((B_ATT, KV, T_TRAIN, HD), BF16),
      ((B_ATT, KV, T_TRAIN, HD), BF16), ((B_ATT, H, T_TRAIN, HD), BF16),
      ((B_ATT, H, T_TRAIN), F32), ((B_ATT, H, T_TRAIN, HD), BF16)]),
    ("flash_prefill_offsets",
     lambda q, k, v, off: flash_attention_pallas(q, k, v, kv_offsets=off),
     [((4, H, 512, HD), BF16), ((4, KV, 512, HD), BF16),
      ((4, KV, 512, HD), BF16), ((4,), I32)]),
    # the engine's batch-1 admission prefill of a ragged 300-token prompt
    ("flash_prefill_ragged", flash_attention_pallas,
     [((1, H, 300, HD), BF16), ((1, KV, 300, HD), BF16),
      ((1, KV, 300, HD), BF16)]),
    ("flash_decode_rope",
     lambda q, k, v, pos, off: flash_decode_pallas(
         q, k, v, pos, offsets=off, rope_theta=ROPE_THETA),
     [((SLOTS, H, HD), BF16), ((SLOTS, KV, 4096, HD), BF16),
      ((SLOTS, KV, 4096, HD), BF16), ((SLOTS,), I32), ((SLOTS,), I32)]),
    ("flash_decode_paged_rope",
     lambda q, kp, vp, pt, pos: flash_decode_paged_pallas(
         q, kp, vp, pt, pos, rope_theta=ROPE_THETA),
     [((SLOTS, H, HD), BF16), ((PAGES, KV, PAGE, HD), BF16),
      ((PAGES, KV, PAGE, HD), BF16), ((SLOTS, 32), I32), ((SLOTS,), I32)]),
    ("flash_decode_paged_int8_rope",
     lambda q, kp, vp, pt, pos, ks, vs: flash_decode_paged_pallas(
         q, kp, vp, pt, pos, k_scale=ks, v_scale=vs, rope_theta=ROPE_THETA),
     [((SLOTS, H, HD), BF16), ((PAGES, KV, PAGE, HD), jnp.int8),
      ((PAGES, KV, PAGE, HD), jnp.int8), ((SLOTS, 32), I32), ((SLOTS,), I32),
      ((PAGES, KV, PAGE), F32), ((PAGES, KV, PAGE), F32)]),
    ("swiglu_fwd", swiglu_pallas,
     [((N_ROWS, D), BF16), ((D, FF), BF16), ((D, FF), BF16)]),
    ("swiglu_bwd", swiglu_backward_pallas,
     [((N_ROWS, D), BF16), ((D, FF), BF16), ((D, FF), BF16),
      ((N_ROWS, FF), BF16), ((N_ROWS, FF), BF16)]),
    ("rmsnorm_residual_fwd", rmsnorm_residual_pallas,
     [((N_ROWS, D), BF16), ((N_ROWS, D), BF16), ((D,), F32)]),
    ("rmsnorm_residual_bwd", rmsnorm_residual_backward_pallas,
     [((N_ROWS, D), BF16), ((D,), F32), ((N_ROWS, D), BF16),
      ((N_ROWS, D), BF16)]),
    ("mamba_chunk_fwd",
     functools.partial(mamba_chunk_pallas, di_tile=512),
     [((1, CHUNK, DI), F32), ((1, CHUNK, DI), F32), ((1, CHUNK, DS), F32),
      ((1, CHUNK, DS), F32), ((DI, DS), F32), ((1, DI, DS), F32)]),
]


@pytest.mark.parametrize("name,fn,shapes", CASES, ids=[c[0] for c in CASES])
def test_kernel_compiles_for_v5e(one_chip, name, fn, shapes):
    text = _compile(fn, one_chip, *shapes)
    assert "tpu_custom_call" in text, name
