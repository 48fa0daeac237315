"""The program's entry points: the compile-cache helper they share, and
``chip_smoke.py``, which must refuse to run anywhere but on a TPU and whose
phases are exercised here at a tiny size on the CPU (kernels in interpret
mode), so the script keeps up with the APIs it drives."""
import dataclasses
import os
import subprocess
import sys
import warnings
from pathlib import Path

import jax
import pytest

from repro.configs.paper_models import RESNET44_CIFAR10
from repro.configs.registry import get_config
from repro.kernels.ops import KernelFallbackWarning
from repro.launch import compile_cache

pytestmark = pytest.mark.tier1

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_var_wins(monkeypatch, restore_cache_dir):
    """A set JAX_COMPILATION_CACHE_DIR is used as is; the helper sets no
    directory of its own."""
    monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/cache")
    was = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == was


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    # a fixed path: the same on every call, in every process
    assert compile_cache.enable_compile_cache() == path


_WRITES_AN_ENTRY = """
import os, sys
import jax, jax.numpy as jnp
from repro.launch import compile_cache
jax.jit(lambda x: x - 1)(jnp.ones(3)).block_until_ready()   # a compile first
if sys.argv[1] != "env":
    compile_cache.DEFAULT_DIR = compile_cache.Path(sys.argv[2])
where = compile_cache.enable_compile_cache()
assert where == sys.argv[2], where
jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
print(len(os.listdir(where)))
"""


@pytest.mark.parametrize("source", ["env", "default"])
def test_compile_cache_writes_an_entry(tmp_path, source):
    """A compile after ``enable_compile_cache()`` lands in the cache
    directory, from the variable or from the helper's own default, even
    when the process compiled something before the call."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=str(REPO / "src"))
    env.pop(compile_cache.ENV_VAR, None)
    if source == "env":
        env[compile_cache.ENV_VAR] = str(tmp_path)
    proc = subprocess.run([sys.executable, "-c", _WRITES_AN_ENTRY, source,
                           str(tmp_path)], env=env, capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert int(proc.stdout.split()[-1]) >= 1


def _smoke(cwd: Path, *, with_src: bool) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    if with_src:
        env["PYTHONPATH"] = str(REPO / "src")
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=str(cwd),
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_chip_smoke_refuses_the_cpu():
    proc = _smoke(REPO, with_src=True)
    assert proc.returncode != 0
    assert "no TPU attached" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_chip_smoke_alone_fails(tmp_path):
    (tmp_path / "chip_smoke.py").write_text(
        (REPO / "chip_smoke.py").read_text())
    proc = _smoke(tmp_path, with_src=False)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


@pytest.fixture
def chip_smoke(monkeypatch):
    """The script's module, with the Mosaic check off (interpret-mode
    kernels compile to no ``tpu_custom_call``) and kernel fallbacks
    promoted to errors as the script promotes them."""
    monkeypatch.syspath_prepend(str(REPO))
    import chip_smoke as cs
    monkeypatch.setattr(cs, "require_kernel", lambda phase, compiled: None)
    with warnings.catch_warnings():
        warnings.simplefilter("error", KernelFallbackWarning)
        yield cs


TINY_VISION = dataclasses.replace(RESNET44_CIFAR10, input_shape=(8, 8, 3),
                                  blocks_per_stage=1)
TINY_LM = dataclasses.replace(get_config("qwen3-1.7b").reduced(),
                              vocab_size=512)


@pytest.mark.parametrize("phase", ["gbn_train", "lm_train", "serve"])
def test_chip_smoke_phase_tiny_on_cpu(chip_smoke, phase, capsys):
    run = {
        "gbn_train": lambda: chip_smoke.gbn_train(0, cfg=TINY_VISION,
                                                  batch=256, steps=2),
        "lm_train": lambda: chip_smoke.lm_train(0, cfg=TINY_LM, rows=2,
                                                seq=64, steps=2,
                                                ce_chunk=128),
        "serve": lambda: chip_smoke.serve(0, cfg=TINY_LM,
                                          prompt_lens=(8, 13, 16),
                                          new_tokens=4, slots=2),
    }[phase]
    run()
    out = capsys.readouterr().out.splitlines()
    assert out and all(line.startswith("[chip run] ") for line in out)
    assert "ok=False" not in "\n".join(out)
