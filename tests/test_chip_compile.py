"""The main-path Pallas kernels compiled by Mosaic for a described TPU v5e.

Nothing runs: each test lowers a kernel at a registry model's real widths
for one chip of a described (not attached) `v5e:2x2` topology and checks
that the compiled program holds the Mosaic kernel (`tpu_custom_call`), so
a shape the chip's compiler refuses (unaligned tiles, too much VMEM) fails
here instead of on the chip. About two seconds per compile.

The topology is described only inside the module fixture: describing it
loads the TPU library, which one process at a time may hold, so it must
never happen while a module is imported.
"""

from __future__ import annotations

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.systolic_gemm.ops import (grouped_gemm, systolic_gemm,
                                             systolic_gemm_t)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described chip's executables can be written to the persistent
    # cache but never read back without the chip: keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure to describe
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


# yi-6b: d_model 4096, d_ff 11008, 4 kv heads x 128, vocab 64000 (untied);
# decode M = 4 slots, prefill M = 4 slots x 512-token bucket
YI_DECODE, YI_PREFILL = 4, 4 * 512
GEMM_CASES = {
    "yi6b-decode-up": (YI_DECODE, 4096, 11008),
    "yi6b-decode-down": (YI_DECODE, 11008, 4096),
    "yi6b-decode-kv": (YI_DECODE, 4096, 512),
    "yi6b-decode-lm-head": (YI_DECODE, 4096, 64000),
    "yi6b-prefill-up": (YI_PREFILL, 4096, 11008),
    "yi6b-prefill-down": (YI_PREFILL, 11008, 4096),
}


def _compiled_text(fn, shapes, one_chip, **kw) -> str:
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
            for s in shapes]
    return fn.lower(*args, interpret=False, out_dtype=jnp.bfloat16,
                    **kw).compile().as_text()


@pytest.mark.parametrize("mkn", list(GEMM_CASES.values()),
                         ids=list(GEMM_CASES))
def test_systolic_gemm_compiles_for_v5e(one_chip, mkn):
    m, k, n = mkn
    assert "tpu_custom_call" in _compiled_text(
        systolic_gemm, [(m, k), (k, n)], one_chip)


def test_systolic_gemm_t_compiles_for_v5e_mamba2_tied_head(one_chip):
    # mamba2-370m's tied LM head streams the stored [vocab 50288, d 1024]
    # table (vocab not a multiple of the 128-lane tile: the last block
    # overhangs it, and the table is not padded)
    text = _compiled_text(systolic_gemm_t, [(64, 1024), (50288, 1024)],
                          one_chip)
    assert "tpu_custom_call" in text and " pad(" not in text


def test_grouped_gemm_compiles_for_v5e_dbrx_experts(one_chip):
    # dbrx-132b: 16 experts, each [d 6144, d_ff 10752], 128 tokens apiece
    assert "tpu_custom_call" in _compiled_text(
        grouped_gemm, [(16, 128, 6144), (16, 6144, 10752)], one_chip,
        activation="silu")


@pytest.mark.parametrize("mkn", [GEMM_CASES["yi6b-decode-up"],
                                 GEMM_CASES["yi6b-decode-down"],
                                 GEMM_CASES["yi6b-prefill-up"]],
                         ids=["decode-up", "decode-down", "prefill-up"])
def test_stacked_systolic_gemm_compiles_for_v5e(one_chip, mkn):
    """The layer index as a scalar-prefetch operand: the kernel takes the
    whole [32, K, N] stack, and nothing slices it."""
    m, k, n = mkn
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
            for s in ((m, k), (32, k, n))]
    i = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    text = jax.jit(lambda x, w, i: systolic_gemm(
        x, w, layer=i, interpret=False, out_dtype=jnp.bfloat16)).lower(
        *args, i).compile().as_text()
    assert "tpu_custom_call" in text and "dynamic-slice" not in text


@pytest.mark.parametrize("mkn", [(64, 1024, 4384), (64 * 512, 1024, 4384),
                                 (64, 2048, 1024)],
                         ids=["decode-in_proj", "prefill-in_proj",
                              "decode-out_proj"])
def test_stacked_gemm_overhanging_n_compiles_for_v5e(one_chip, mkn):
    """mamba2-370m's projections from their [48, K, N] stacks: in_proj's
    N = 4384 is no multiple of 128, so its last block overhangs N, and
    still nothing slices or pads the stack."""
    m, k, n = mkn
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
            for s in ((m, k), (48, k, n))]
    i = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    text = jax.jit(lambda x, w, i: systolic_gemm(
        x, w, layer=i, interpret=False, out_dtype=jnp.bfloat16)).lower(
        *args, i).compile().as_text()
    assert "tpu_custom_call" in text
    assert "dynamic-slice" not in text and " pad(" not in text


_INST = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (\(.*?\)|\S+) "
                   r"([a-z][a-z\-]*)\((.*)$")


def _readers(text: str, stacks) -> dict:
    """(op kind, custom-call target) -> count, over the instructions of a
    compiled HLO module that take an array of a shape in `stacks`."""
    shape_of, seen = {}, {}
    for line in text.splitlines():
        m = _INST.match(line)
        if not m:
            continue
        name, result, kind, rest = m.groups()
        dims = re.match(r"[a-z0-9]+\[([0-9,]*)\]", result)
        if dims:
            shape_of[name] = tuple(int(d) for d in dims.group(1).split(",")
                                   if d)
        if kind in ("parameter", "get-tuple-element", "tuple", "while",
                    "bitcast"):
            continue
        operands = re.findall(r"%([\w.\-]+)", rest.split("), ")[0])
        if any(shape_of.get(o) in stacks for o in operands):
            target = re.search(r'custom_call_target="([^"]+)"', rest)
            key = (kind, target.group(1) if target else "")
            seen[key] = seen.get(key, 0) + 1
    return seen


def test_served_decode_chunk_streams_stacked_weights(one_chip, monkeypatch):
    """The engine's decode chunk of a small yi-6b-shaped model compiled for
    the chip: every projection's stack (the MLP's; q/k/v in their stored
    [d, H, hd] layout; o, and k/v of one head, through a bitcast)
    reaches only the pod GEMM's custom calls, and nothing slices, copies
    or relayouts a stack.

    Widths are multiples of 128 (2 layers, d 256, d_ff 512, 2 heads / 1
    KV head of 128) because the chip stores an array whose minor dimension
    is narrower in another layout (tiny-dense's [2, 128, 64] down stack is
    stored {1,2,0}), which a kernel reading row-major blocks would have to
    copy. The compiler may still prefetch a stack this small whole into
    fast memory (copy-start), which a full-width stack never fits."""
    import dataclasses

    import repro.kernels.systolic_gemm.ops as ops
    from repro.configs import get_arch, reduced
    from repro.models.model import Model
    from repro.serve.engine import ServeEngine
    jax.clear_caches()                    # no CPU-traced kernel reused
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    cfg = dataclasses.replace(reduced(get_arch("yi-6b")), d_model=256,
                              d_ff=512, n_heads=2, n_kv_heads=1,
                              head_dim=128, vocab=512)
    L, d, ff, hd = cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.resolved_head_dim
    model = Model(cfg, use_pallas=True)
    pshapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    engine = ServeEngine(model, pshapes, slots=3, max_len=64, decode_chunk=8)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    lanes = jax.ShapeDtypeStruct((3,), jnp.int32, sharding=one_chip)
    text = engine._decode_fn.lower(
        on_chip(pshapes), on_chip(engine.cache), lanes, lanes, lanes,
        jax.ShapeDtypeStruct((3,), jnp.bool_, sharding=one_chip),
        n=8).compile().as_text()
    jax.clear_caches()
    stacks = {(L, d, ff), (L, ff, d), (L, cfg.n_heads, hd, d),
              (L, cfg.n_heads * hd, d), (L, d, cfg.n_heads, hd),
              (L, d, cfg.n_kv_heads, hd), (L, d, cfg.n_kv_heads * hd)}
    readers = _readers(text, stacks)
    readers.pop(("copy-start", ""), None)
    # gate, up, down, q, k, v, o
    assert readers == {("custom-call", "tpu_custom_call"): 7}
    # no op makes a stack-sized array (a copy or relayout of a stack)
    sizes = {L * d * ff, L * d * d, L * d * cfg.n_kv_heads * hd}
    made = [m.group(2) for m in map(_INST.match, text.splitlines())
            if m and m.group(3) in ("copy", "transpose", "fusion")]
    for result in made:
        dims = re.match(r"[a-z0-9]+\[([0-9,]*)\]", result)
        if dims:
            shape = [int(x) for x in dims.group(1).split(",") if x]
            assert not (shape[:1] == [L] and math.prod(shape) in sizes), \
                result


def test_mamba2_served_programs_fit_one_v5e(one_chip, monkeypatch):
    """mamba2-370m at published widths (48 layers, d_model 1024, 32 SSD
    heads of 64 x 128, vocab 50288) behind the engine of its benchmark
    cell (64 slots x 1024): the decode chunk of 8 steps and the prefill of
    a [64, 512] bucket compiled for one chip, each within its 16 GB with
    the parameters and the cache it takes (bytes recorded in
    bench/configs/mamba2-370m.json's notes), and in_proj, out_proj and
    the tied head on the pod GEMM in each. The prefill the engine runs at
    bucket 512 is [W(512), 512] = [1, 512] (the width comes from the
    tokens' shape, so [64, 512] still lowers), and it needs fewer bytes."""
    import repro.kernels.systolic_gemm.ops as ops
    from repro.configs import get_arch
    from repro.models.model import Model
    from repro.serve.engine import ServeEngine
    jax.clear_caches()
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    model = Model(get_arch("mamba2-370m"), use_pallas=True)
    pshapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    engine = ServeEngine(model, pshapes, slots=64, max_len=1024,
                         decode_chunk=8)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    params, cache = on_chip(pshapes), on_chip(engine.cache)
    progs = [
        engine._decode_fn.lower(params, cache, i32(64), i32(64), i32(64),
                                jax.ShapeDtypeStruct((64,), jnp.bool_,
                                                     sharding=one_chip),
                                n=8),
        engine._prefill_fn.lower(params, i32(64, 512), cache, i32(64),
                                 i32(64)),
    ]
    w = engine._width(512)
    assert w == 1
    progs.append(engine._prefill_fn.lower(params, i32(w, 512), cache,
                                          i32(w), i32(w)))
    needs = []
    for low in progs:
        compiled = low.compile()
        m = compiled.memory_analysis()
        need = (m.argument_size_in_bytes + m.temp_size_in_bytes
                + m.output_size_in_bytes - m.alias_size_in_bytes)
        assert need < 16 * 10 ** 9, need
        assert compiled.as_text().count("tpu_custom_call") == 3
        needs.append(need)
    assert needs[2] < needs[1], needs
    jax.clear_caches()
