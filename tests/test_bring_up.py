"""The rules that keep the served path honest about its device: where the
kernels run, where the compile cache goes, how full-width params are made,
how `launch.serve` reports failure, and that `chip_smoke.py` never passes
off the chip."""

from __future__ import annotations

import math
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.kernels import backend
from repro.launch import compile_cache
from repro.models.layers import init_from_schema, is_spec
from repro.models.model import Model

ROOT = Path(__file__).resolve().parents[1]


# -- one device rule for the kernels --------------------------------------

@pytest.mark.parametrize("platform,interpret",
                         [("cpu", True), ("tpu", False)])
def test_interpret_only_on_cpu_backend(monkeypatch, platform, interpret):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert backend.interpret_mode() is interpret


@pytest.mark.parametrize("platform", ["gpu", "cuda", "rocm", "METAL"])
def test_unknown_backend_raises_and_names_it(monkeypatch, platform):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    with pytest.raises(RuntimeError, match=repr(platform)):
        backend.interpret_mode()


def test_kernel_ops_share_the_one_rule():
    from repro.kernels.flash_attention import ops as fa
    from repro.kernels.ssd import ops as ssd
    from repro.kernels.systolic_gemm import ops as gemm
    for mod in (fa, ssd, gemm):
        assert mod.interpret_mode is backend.interpret_mode


# -- compile cache placed from outside ------------------------------------

@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_leaves_env_dir_alone(monkeypatch, tmp_path,
                                            cache_config):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None


def test_compile_cache_defaults_to_one_checkout_dir(monkeypatch,
                                                    cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.use_compile_cache()
    second = compile_cache.use_compile_cache()
    assert first == second == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


# -- jitted init: bit-identical to the eager draw -------------------------

def _eager_init(rng, schema):
    """The eager per-leaf init the jitted one replaced, kept as the
    reference: same keys, float32 draw, fan-in scale, cast."""
    leaves, treedef = jax.tree_util.tree_flatten(schema, is_leaf=is_spec)
    keys = jax.random.split(rng, len(leaves))
    vals = []
    for key, spec in zip(keys, leaves):
        if spec.init == "zeros":
            vals.append(jnp.zeros(spec.shape, spec.dtype))
        elif spec.init == "ones":
            vals.append(jnp.ones(spec.shape, spec.dtype))
        else:
            scale = spec.scale
            if scale is None:
                fan_in = spec.shape[-2] if len(spec.shape) >= 2 \
                    else spec.shape[-1]
                scale = 1.0 / math.sqrt(max(1, fan_in))
            vals.append((jax.random.normal(key, spec.shape, jnp.float32)
                         * scale).astype(spec.dtype))
    return jax.tree_util.tree_unflatten(treedef, vals)


@pytest.mark.parametrize("arch", ["yi-6b", "mamba2-370m", "dbrx-132b",
                                  "hymba-1.5b"])
def test_jitted_init_is_bit_identical_to_eager(arch):
    schema = Model(reduced(get_arch(arch))).schema()
    rng = jax.random.PRNGKey(3)
    got = init_from_schema(rng, schema)
    want = _eager_init(rng, schema)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(
            np.asarray(g).view(np.uint8), np.asarray(w).view(np.uint8))


# -- launch.serve: pod GEMM path, non-zero exit on non-finite logits -------

SERVE_ARGS = ["--reduced", "--requests", "2", "--slots", "2",
              "--max-new", "3", "--max-len", "64"]


@pytest.fixture
def serve_mod(monkeypatch, tmp_path):
    from repro.launch import serve
    # an env-named cache dir keeps main() off this process's jax config
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    return serve


def test_serve_main_runs_the_pod_gemm(serve_mod, monkeypatch, capsys):
    built = []
    build = serve_mod.build_engine

    def spy(*a, **kw):
        built.append(build(*a, **kw))
        return built[-1]
    monkeypatch.setattr(serve_mod, "build_engine", spy)
    assert serve_mod.main(SERVE_ARGS) == 0
    assert built[0].model.use_pallas
    out = capsys.readouterr().out
    assert "wall on cpu cpu x1" in out


def test_serve_main_exits_nonzero_on_non_finite_logits(serve_mod,
                                                       monkeypatch, capsys):
    build = serve_mod.build_engine

    def poisoned(*a, **kw):
        eng = build(*a, **kw)
        eng.params = jax.tree.map(lambda p: p * jnp.nan, eng.params)
        return eng
    monkeypatch.setattr(serve_mod, "build_engine", poisoned)
    assert serve_mod.main(SERVE_ARGS) == 1
    assert "non-finite logits shed 2 request(s)" in capsys.readouterr().out


# -- chip_smoke.py never passes off the chip ---------------------------------

def _smoke(tmp_path, *args, cwd=ROOT, script=ROOT / "chip_smoke.py"):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script), *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=600)


def test_chip_smoke_fails_on_cpu(tmp_path):
    p = _smoke(tmp_path)
    assert p.returncode != 0
    assert '"ok": true' not in p.stdout
    assert "no TPU" in p.stdout


def test_chip_smoke_fails_alone_outside_the_repo(tmp_path):
    lone = tmp_path / "lone"
    lone.mkdir()
    (lone / "chip_smoke.py").write_text((ROOT / "chip_smoke.py").read_text())
    p = _smoke(tmp_path, cwd=lone, script=lone / "chip_smoke.py")
    assert p.returncode != 0
    assert '"ok": true' not in p.stdout


def test_chip_smoke_cpu_rehearsal_passes_phases_but_never_ok(tmp_path):
    p = _smoke(tmp_path, "--reduced")
    assert p.returncode != 0, p.stdout + p.stderr
    assert '"ok": true' not in p.stdout
    phases = [ln for ln in p.stdout.splitlines() if ln.startswith("[")]
    assert {ln[1] for ln in phases} >= set("abcd")
    fails = [ln for ln in phases if " FAIL " in ln]
    assert fails == ["[end] FAIL rehearsal on 'cpu': not a chip run"], \
        p.stdout + p.stderr
