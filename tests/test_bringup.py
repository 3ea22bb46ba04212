"""What ``chip_smoke.py`` cannot re-check on every PR, pinned on the CPU:
where the compile cache goes, where a served model's state lives, and
that the smoke refuses to run without a chip."""

import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest

from singa_tpu import device, tensor
from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from singa_tpu.serve import (GenerationRequest, PagedConfig,
                             PrefixCacheConfig)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_py(code, **env):
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, "-c", code], cwd=REPO, env=full,
                          capture_output=True, text=True, timeout=120)


_PRINT_CACHE_DIR = ("import jax, singa_tpu; "
                    "print(jax.config.jax_compilation_cache_dir)")


def test_compile_cache_dir_from_environment(tmp_path):
    r = _run_py(_PRINT_CACHE_DIR, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == str(tmp_path)


def test_compile_cache_dir_default_is_fixed_in_checkout():
    r = _run_py(_PRINT_CACHE_DIR)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_is_set_in_exactly_one_place():
    pat = re.compile(r"compilation_cache|JAX_COMPILATION_CACHE_DIR")
    hits = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("tests", "chiprun_out", "__pycache__")]
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(root, name)
                with open(path, encoding="utf-8") as f:
                    if pat.search(f.read()):
                        hits.append(os.path.relpath(path, REPO))
    # chip_smoke.py only READS the setting, to print where the cache is
    assert sorted(hits) == ["chip_smoke.py", "singa_tpu/__init__.py"]
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        assert "config.update" not in f.read()


def test_package_imports_clean_under_deprecation_errors():
    r = subprocess.run(
        [sys.executable, "-W", "error::DeprecationWarning", "-c",
         "import singa_tpu"], cwd=REPO, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=120)
    assert r.returncode == 0, r.stderr


@pytest.mark.parametrize("kw", [
    dict(),
    dict(paged=PagedConfig(block_size=8, num_blocks=16)),
    dict(prefix_cache=PrefixCacheConfig(block_size=8, num_blocks=16)),
], ids=["slot", "paged", "prefix"])
def test_engine_state_lives_with_the_weights(kw):
    """A model built on device D (not the process default) serves from
    D: params, arenas/pools, key table and a step's outputs."""
    dev = device.create_tpu_device(1)
    d = dev.jax_device
    assert d != jax.devices()[0]
    m = GPT2LMHead(GPT2Config.tiny(dropout=0.0))
    m.compile([tensor.from_numpy(np.zeros((1, 16), np.int32), dev)],
              is_train=False, use_graph=False)
    eng = m.serve(max_slots=2, **kw)
    try:
        def held():
            trees = [eng._params, eng._keys]
            if eng.paged_arena is not None:
                trees += [eng.paged_arena.pool_k, eng.paged_arena.pool_v]
            else:
                trees += [eng._kc, eng._vc]
            if eng.prefix_cache is not None \
                    and eng.prefix_cache._pool_k is not None:
                trees += [eng.prefix_cache._pool_k,
                          eng.prefix_cache._pool_v]
            return jax.tree.leaves(trees)

        assert all(a.devices() == {d} for a in held())
        h = eng.submit(GenerationRequest(
            np.arange(1, 12, dtype=np.int32), max_new_tokens=4,
            temperature=0.0, seed=0))
        eng.run_until_complete(max_steps=50)
        assert len(h.result().tokens) == 11 + 4
        # every array the steps wrote back is still on D
        assert all(a.devices() == {d} for a in held())
    finally:
        eng.close()


def test_chip_smoke_refuses_cpu():
    """No chip, no smoke: non-zero exit naming the platform it found,
    before any model is built, and no result line."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert r.returncode != 0
    assert "cpu" in r.stderr
    assert '"ok"' not in r.stdout
    assert "train" not in r.stdout
