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

import os

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
    # mamba2-370m's tied LM head streams the stored [vocab 50280, d 1024]
    # table (vocab not a multiple of the 128-lane tile)
    assert "tpu_custom_call" in _compiled_text(
        systolic_gemm_t, [(4, 1024), (50280, 1024)], one_chip)


def test_grouped_gemm_compiles_for_v5e_dbrx_experts(one_chip):
    # dbrx-132b: 16 experts, each [d 6144, d_ff 10752], 128 tokens apiece
    assert "tpu_custom_call" in _compiled_text(
        grouped_gemm, [(16, 128, 6144), (16, 6144, 10752)], one_chip,
        activation="silu")
