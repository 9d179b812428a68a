"""Per-kernel validation: shape/dtype sweeps + hypothesis property tests,
all against the pure-jnp ref.py oracles (interpret=True on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # degrade gracefully: deterministic fallback
    from _hypothesis_fallback import given, settings, st

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.ssd.ops import ssd
from repro.kernels.ssd.ref import ssd_ref
from repro.kernels.systolic_gemm.ops import (fused_lane_gemm,
                                             fused_lane_gemm_t, grouped_gemm,
                                             systolic_gemm, systolic_gemm_t)
from repro.kernels.systolic_gemm.ref import (systolic_gemm_ref,
                                             systolic_gemm_t_ref)

RNG = np.random.default_rng(42)


# --------------------------------------------------------------------------
# systolic GEMM
# --------------------------------------------------------------------------

GEMM_SHAPES = [(64, 64, 64), (128, 256, 128), (100, 130, 70), (1, 1, 1),
               (33, 257, 129), (8, 1024, 8), (512, 64, 512)]


@pytest.mark.parametrize("shape", GEMM_SHAPES)
@pytest.mark.parametrize("dtype", ["int8", "bfloat16", "float32"])
def test_systolic_gemm_shapes(shape, dtype):
    M, K, N = shape
    if dtype == "int8":
        x = jnp.asarray(RNG.integers(-100, 100, (M, K)), jnp.int8)
        w = jnp.asarray(RNG.integers(-100, 100, (K, N)), jnp.int8)
        tol = 1e-5
    else:
        x = jnp.asarray(RNG.standard_normal((M, K)), dtype)
        w = jnp.asarray(RNG.standard_normal((K, N)), dtype)
        tol = 2e-2 if dtype == "bfloat16" else 1e-5
    out = systolic_gemm(x, w, interpret=True)
    ref = systolic_gemm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("act", [None, "relu", "gelu", "silu", "relu2"])
def test_systolic_gemm_epilogue(act):
    """The fused post-processor epilogue (scale + bias + activation)."""
    M, K, N = 96, 160, 72
    x = jnp.asarray(RNG.integers(-64, 64, (M, K)), jnp.int8)
    w = jnp.asarray(RNG.integers(-64, 64, (K, N)), jnp.int8)
    s = jnp.asarray(RNG.random(N) * 0.1, jnp.float32)
    b = jnp.asarray(RNG.standard_normal(N), jnp.float32)
    out = systolic_gemm(x, w, s, b, activation=act, interpret=True)
    ref = systolic_gemm_ref(x, w, s, b, activation=act)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("blocks", [(64, 64, 64), (128, 64, 256), (32, 128, 32)])
def test_systolic_gemm_block_invariance(blocks):
    """SOSA pillar 1 as a property: the result must be invariant to the pod
    (block) granularity — only throughput/Watt changes, never the math."""
    bm, bn, bk = blocks
    M, K, N = 160, 192, 136
    x = jnp.asarray(RNG.integers(-50, 50, (M, K)), jnp.int8)
    w = jnp.asarray(RNG.integers(-50, 50, (K, N)), jnp.int8)
    out = systolic_gemm(x, w, block_m=bm, block_n=bn, block_k=bk,
                        interpret=True)
    ref = systolic_gemm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


@settings(max_examples=20, deadline=None)
@given(m=st.integers(1, 80), k=st.integers(1, 80), n=st.integers(1, 80))
def test_systolic_gemm_property(m, k, n):
    x = jnp.asarray(RNG.integers(-8, 8, (m, k)), jnp.int8)
    w = jnp.asarray(RNG.integers(-8, 8, (k, n)), jnp.int8)
    out = systolic_gemm(x, w, block_m=32, block_n=32, block_k=32,
                        interpret=True)
    ref = systolic_gemm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


# --------------------------------------------------------------------------
# stacked weights: the layer's blocks read straight from [L, K, N]
# --------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["int8", "bfloat16"])
def test_systolic_gemm_stacked_layer_matches_slice(dtype):
    """`layer=i` on a stack gives what the slice w[i] gives, bit for bit,
    at the same blocks, for every layer."""
    L, M, K, N = 3, 8, 256, 384
    if dtype == "int8":
        x = jnp.asarray(RNG.integers(-100, 100, (M, K)), jnp.int8)
        w = jnp.asarray(RNG.integers(-100, 100, (L, K, N)), jnp.int8)
    else:
        x = jnp.asarray(RNG.standard_normal((M, K)), jnp.bfloat16)
        w = jnp.asarray(RNG.standard_normal((L, K, N)), jnp.bfloat16)
    blocks = dict(block_m=8, block_n=128, block_k=128)
    for i in range(L):
        out = systolic_gemm(x, w, layer=jnp.int32(i), activation="silu",
                            interpret=True, **blocks)
        ref = systolic_gemm(x, w[i], activation="silu", interpret=True,
                            **blocks)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("heads,hd", [(16, 128), (4, 128), (4, 16)],
                         ids=["16x128", "4x128", "4x16-per-layer"])
def test_systolic_gemm_stacked_heads_read_in_stored_layout(heads, hd):
    """A stack [L, K, H, hd] (q/k/v as stored) gives what the layer's
    [K, H * hd] reshape gives, bit for bit at the same blocks. Heads
    narrower than the 128 lanes take the per-layer path."""
    from repro.parallel.autoshard import choose_blocks
    L, M, K = 2, 8, 256
    x = jnp.asarray(RNG.standard_normal((M, K)), jnp.bfloat16)
    w = jnp.asarray(RNG.standard_normal((L, K, heads, hd)), jnp.bfloat16)
    bm, bn, bk = choose_blocks(M, K, heads * hd, out_bytes=2, head_dim=hd)
    for i in range(L):
        out = systolic_gemm(x, w, layer=jnp.int32(i), out_dtype=jnp.bfloat16,
                            interpret=True)
        ref = systolic_gemm(x, w[i].reshape(K, -1), out_dtype=jnp.bfloat16,
                            block_m=bm, block_n=bn, block_k=bk,
                            interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_systolic_gemm_stacked_without_dividing_blocks_slices_the_layer():
    """K = 300 has no block that divides it (the autotuner pads it), so the
    stacked call takes the per-layer path: the layer is sliced out, padded
    and multiplied, and the result still matches."""
    L, M, K, N = 2, 8, 300, 256
    x = jnp.asarray(RNG.standard_normal((M, K)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((L, K, N)), jnp.float32)
    lowered = jax.jit(lambda x, w, i: systolic_gemm(
        x, w, layer=i, interpret=True)).lower(x, w, jnp.int32(1)).as_text()
    assert "dynamic_slice" in lowered
    for i in range(L):
        out = systolic_gemm(x, w, layer=jnp.int32(i), interpret=True)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(systolic_gemm(x, w[i],
                                                      interpret=True)))
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(systolic_gemm_ref(x, w[i])),
                                   rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("k,n", [(1024, 4384), (2048, 1024)],
                         ids=["in_proj", "out_proj"])
def test_systolic_gemm_streams_mamba2_stacks_in_place(k, n):
    """mamba2-370m's in_proj [L, 1024, 4384] (N no multiple of 128: the
    last block overhangs N) and out_proj [L, 2048, 1024], read from the
    stack at the autotuner's blocks, equal jnp.dot of the layer."""
    from repro.kernels.systolic_gemm.ops import _auto_blocks, _streams
    L, M = 2, 64
    x = jnp.asarray(RNG.standard_normal((M, k)), jnp.bfloat16)
    w = jnp.asarray(RNG.standard_normal((L, k, n)), jnp.bfloat16)
    assert _streams(w, n, _auto_blocks(M, k, n, x.dtype, jnp.float32))
    for i in range(L):
        out = systolic_gemm(x, w, layer=jnp.int32(i), interpret=True)
        ref = jnp.dot(x, w[i], preferred_element_type=jnp.float32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-3)


def test_systolic_gemm_stacked_under_scan_with_traced_layer():
    """The scalar-prefetch index works as a scan's traced loop index: one
    call of the scan body reads every layer in turn."""
    L, M, K, N = 4, 16, 128, 256
    x = jnp.asarray(RNG.standard_normal((M, K)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((L, K, N)), jnp.float32)

    def body(h, i):
        y = fused_lane_gemm(h[None], w, layer=i, interpret=True,
                            block_m=16, block_n=128, block_k=128)[0]
        return h, y

    _, ys = jax.jit(lambda x: jax.lax.scan(body, x, jnp.arange(L)))(x)
    for i in range(L):
        np.testing.assert_array_equal(
            np.asarray(ys[i]),
            np.asarray(systolic_gemm(x, w[i], interpret=True, block_m=16,
                                     block_n=128, block_k=128)))


# --------------------------------------------------------------------------
# grouped / fused-lane GEMM variants
# --------------------------------------------------------------------------

GROUPED_SHAPES = [(2, 32, 40, 24), (3, 64, 64, 64), (1, 5, 130, 17),
                  (4, 33, 17, 65)]


@pytest.mark.parametrize("shape", GROUPED_SHAPES)
@pytest.mark.parametrize("dtype", ["int8", "float32"])
def test_grouped_gemm_matches_per_group_ref(shape, dtype):
    """G independent GEMMs in one launch == per-group oracle."""
    G, M, K, N = shape
    if dtype == "int8":
        x = jnp.asarray(RNG.integers(-50, 50, (G, M, K)), jnp.int8)
        w = jnp.asarray(RNG.integers(-50, 50, (G, K, N)), jnp.int8)
    else:
        x = jnp.asarray(RNG.standard_normal((G, M, K)), jnp.float32)
        w = jnp.asarray(RNG.standard_normal((G, K, N)), jnp.float32)
    out = grouped_gemm(x, w, interpret=True)
    ref = jnp.stack([systolic_gemm_ref(x[g], w[g]) for g in range(G)])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


def test_grouped_gemm_per_group_epilogue():
    """Per-group dequant scale + bias + activation (the SIMD
    post-processor, one per pod group)."""
    G, M, K, N = 3, 24, 48, 40
    x = jnp.asarray(RNG.integers(-40, 40, (G, M, K)), jnp.int8)
    w = jnp.asarray(RNG.integers(-40, 40, (G, K, N)), jnp.int8)
    s = jnp.asarray(RNG.random((G, N)) * 0.1, jnp.float32)
    b = jnp.asarray(RNG.standard_normal((G, N)), jnp.float32)
    out = grouped_gemm(x, w, s, b, activation="silu", interpret=True)
    ref = jnp.stack([systolic_gemm_ref(x[g], w[g], s[g], b[g],
                                       activation="silu")
                     for g in range(G)])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


def test_fused_lane_gemm_collapses_leading_axes():
    """[B, S, K] @ [K, N] runs as one (B*S, K) GEMM — the fused decode-lane
    shape — and restores the leading axes."""
    x = jnp.asarray(RNG.standard_normal((4, 3, 32)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((32, 24)), jnp.float32)
    out = fused_lane_gemm(x, w, interpret=True)
    assert out.shape == (4, 3, 24)
    ref = jnp.einsum("bsk,kn->bsn", x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# grouped GEMM edge cases (the shapes MoE capacity-bucket dispatch hits)
# --------------------------------------------------------------------------

def test_grouped_gemm_empty_group_stays_zero():
    """An expert that received no tokens is an all-zero group: its output
    must be exactly zero (no epilogue bleed), neighbours unaffected."""
    G, M, K, N = 3, 16, 32, 24
    x = jnp.asarray(RNG.standard_normal((G, M, K)), jnp.float32)
    x = x.at[1].set(0.0)                       # expert 1: empty bucket
    w = jnp.asarray(RNG.standard_normal((G, K, N)), jnp.float32)
    out = grouped_gemm(x, w, interpret=True)
    np.testing.assert_array_equal(np.asarray(out[1]), 0.0)
    for g in (0, 2):
        np.testing.assert_allclose(np.asarray(out[g]),
                                   np.asarray(systolic_gemm_ref(x[g], w[g])),
                                   rtol=1e-5, atol=1e-5)


def test_grouped_gemm_ragged_fill():
    """Capacity buckets are ragged: each group has a different number of
    real rows, the rest zero-padded. Real rows must match the per-group
    oracle, padded rows stay exactly zero (rows are independent in a
    GEMM — the invariant the MoE scatter dispatch relies on)."""
    G, M, K, N = 4, 12, 20, 16
    fills = [12, 5, 1, 0]
    x = jnp.asarray(RNG.standard_normal((G, M, K)), jnp.float32)
    mask = (np.arange(M)[None, :] < np.asarray(fills)[:, None])
    x = x * jnp.asarray(mask[..., None], jnp.float32)
    w = jnp.asarray(RNG.standard_normal((G, K, N)), jnp.float32)
    out = np.asarray(grouped_gemm(x, w, interpret=True))
    ref = np.stack([np.asarray(systolic_gemm_ref(x[g], w[g]))
                    for g in range(G)])
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    for g, f in enumerate(fills):
        np.testing.assert_array_equal(out[g, f:], 0.0)


def test_grouped_gemm_single_group_degenerates_to_gemm():
    """G == 1 (single-expert model) must equal the plain pod GEMM."""
    M, K, N = 40, 56, 33
    x = jnp.asarray(RNG.integers(-40, 40, (1, M, K)), jnp.int8)
    w = jnp.asarray(RNG.integers(-40, 40, (1, K, N)), jnp.int8)
    out = grouped_gemm(x, w, interpret=True)
    ref = systolic_gemm(x[0], w[0], interpret=True)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref),
                               rtol=1e-6)


# --------------------------------------------------------------------------
# transposed-weight GEMM (the tied-embedding LM head)
# --------------------------------------------------------------------------

GEMM_T_SHAPES = [(64, 64, 64), (100, 130, 70), (1, 16, 8), (33, 257, 129),
                 (8, 64, 500)]


@pytest.mark.parametrize("shape", GEMM_T_SHAPES)
@pytest.mark.parametrize("dtype", ["int8", "bfloat16", "float32"])
def test_systolic_gemm_t_shapes(shape, dtype):
    """x [M,K] @ w[N,K]^T == oracle, across dtypes and ragged dims."""
    M, K, N = shape
    if dtype == "int8":
        x = jnp.asarray(RNG.integers(-100, 100, (M, K)), jnp.int8)
        w = jnp.asarray(RNG.integers(-100, 100, (N, K)), jnp.int8)
        tol = 1e-5
    else:
        x = jnp.asarray(RNG.standard_normal((M, K)), dtype)
        w = jnp.asarray(RNG.standard_normal((N, K)), dtype)
        tol = 2e-2 if dtype == "bfloat16" else 1e-5
    out = systolic_gemm_t(x, w, interpret=True)
    ref = systolic_gemm_t_ref(x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("act", [None, "silu", "relu2"])
def test_systolic_gemm_t_epilogue(act):
    M, K, N = 48, 80, 56
    x = jnp.asarray(RNG.integers(-64, 64, (M, K)), jnp.int8)
    w = jnp.asarray(RNG.integers(-64, 64, (N, K)), jnp.int8)
    s = jnp.asarray(RNG.random(N) * 0.1, jnp.float32)
    b = jnp.asarray(RNG.standard_normal(N), jnp.float32)
    out = systolic_gemm_t(x, w, s, b, activation=act, interpret=True)
    ref = systolic_gemm_t_ref(x, w, s, b, activation=act)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


def test_fused_lane_gemm_t_is_the_tied_unembed():
    """[B, S, d] against the stored [vocab, d] token table == x @ tok.T —
    the tied-embedding LM head, no transpose copy."""
    vocab, d = 96, 32
    x = jnp.asarray(RNG.standard_normal((2, 5, d)), jnp.float32)
    tok = jnp.asarray(RNG.standard_normal((vocab, d)), jnp.float32)
    out = fused_lane_gemm_t(x, tok, interpret=True)
    assert out.shape == (2, 5, vocab)
    ref = jnp.einsum("bsd,vd->bsv", x, tok)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_unembed_pallas_matches_einsum_tied_and_untied():
    """models.layers.unembed(use_pallas=True): both embedding layouts run
    the pod kernel and match the einsum oracle."""
    from repro.models.layers import embed_schema, init_from_schema, unembed
    x = jnp.asarray(RNG.standard_normal((2, 3, 16)), jnp.float32)
    for tie in (True, False):
        p = init_from_schema(jax.random.PRNGKey(0),
                             embed_schema(50, 16, tie))
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        ref = unembed(p, x)
        out = unembed(p, x, use_pallas=True)
        assert out.dtype == ref.dtype
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------

ATTN_CASES = [
    # B, Sq, Hq, Hkv, D, causal, window
    (2, 64, 4, 2, 32, True, None),
    (1, 100, 8, 8, 16, True, None),
    (2, 33, 4, 1, 64, False, None),
    (1, 128, 5, 5, 32, True, 48),
    (1, 256, 16, 2, 64, True, None),
    (1, 80, 6, 3, 128, True, 16),
]


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(case, dtype):
    B, S, Hq, Hkv, D, causal, win = case
    q = jnp.asarray(RNG.standard_normal((B, S, Hq, D)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, S, Hkv, D)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, S, Hkv, D)), dtype)
    out = flash_attention(q, k, v, causal=causal, window=win,
                          block_q=32, block_k=32, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal, window=win)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_matches_chunked_jax():
    """Kernel == the pure-JAX chunked production path (same blocking)."""
    from repro.models.attention import chunked_attention
    q = jnp.asarray(RNG.standard_normal((2, 96, 8, 32)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((2, 96, 4, 32)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((2, 96, 4, 32)), jnp.float32)
    a = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                        interpret=True)
    b = chunked_attention(q, k, v, causal=True, kv_block=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                               atol=2e-3)


@settings(max_examples=10, deadline=None)
@given(s=st.integers(2, 70), d=st.sampled_from([8, 16, 32]),
       hq=st.sampled_from([1, 2, 4]), causal=st.booleans())
def test_flash_attention_property(s, d, hq, causal):
    q = jnp.asarray(RNG.standard_normal((1, s, hq, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, s, 1, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, s, 1, d)), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-3, atol=3e-3)


# --------------------------------------------------------------------------
# SSD
# --------------------------------------------------------------------------

SSD_CASES = [(2, 64, 4, 16, 1, 32, 16), (1, 100, 2, 8, 2, 16, 32),
             (1, 32, 4, 16, 4, 8, 32), (2, 48, 8, 32, 1, 64, 16)]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_sweep(case):
    b, S, H, P, G, N, chunk = case
    x = jnp.asarray(RNG.standard_normal((b, S, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.random((b, S, H)) * 0.5 + 0.1, jnp.float32)
    A = jnp.asarray(-RNG.random(H) - 0.1, jnp.float32)
    B = jnp.asarray(RNG.standard_normal((b, S, G, N)), jnp.float32)
    C = jnp.asarray(RNG.standard_normal((b, S, G, N)), jnp.float32)
    D = jnp.asarray(RNG.random(H), jnp.float32)
    y, h = ssd(x, dt, A, B, C, D, chunk=chunk, interpret=True)
    rep = H // G
    yr, hr = ssd_ref(x, dt, A, jnp.repeat(B, rep, 2), jnp.repeat(C, rep, 2),
                     D, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), rtol=2e-4,
                               atol=2e-4)


def test_ssd_chunk_invariance():
    """Chunk size is a tiling knob (SOSA pillar 3): must not change the
    result."""
    b, S, H, P, N = 1, 96, 2, 16, 32
    x = jnp.asarray(RNG.standard_normal((b, S, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.random((b, S, H)) * 0.3 + 0.1, jnp.float32)
    A = jnp.asarray(-RNG.random(H) - 0.1, jnp.float32)
    B = jnp.asarray(RNG.standard_normal((b, S, 1, N)), jnp.float32)
    C = jnp.asarray(RNG.standard_normal((b, S, 1, N)), jnp.float32)
    D = jnp.asarray(RNG.random(H), jnp.float32)
    outs = [np.asarray(ssd(x, dt, A, B, C, D, chunk=c, interpret=True)[0])
            for c in (16, 32, 96)]
    np.testing.assert_allclose(outs[0], outs[1], rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(outs[0], outs[2], rtol=5e-4, atol=5e-4)


def test_ssd_decode_consistency():
    """Sequential decode steps == chunked prefill (the serving invariant)."""
    from repro.models.ssm import ssd_decode_step
    b, S, H, P, N = 1, 24, 2, 8, 16
    x = jnp.asarray(RNG.standard_normal((b, S, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.random((b, S, H)) * 0.3 + 0.1, jnp.float32)
    A = jnp.asarray(-RNG.random(H) - 0.1, jnp.float32)
    B = jnp.asarray(RNG.standard_normal((b, S, H, N)), jnp.float32)
    C = jnp.asarray(RNG.standard_normal((b, S, H, N)), jnp.float32)
    D = jnp.asarray(RNG.random(H), jnp.float32)
    y_chunk, h_chunk = ssd_ref(x, dt, A, B, C, D, chunk=8)
    h = jnp.zeros((b, H, P, N), jnp.float32)
    ys = []
    for t in range(S):
        y, h = ssd_decode_step(x[:, t], dt[:, t], A, B[:, t], C[:, t], D, h)
        ys.append(y)
    y_seq = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_seq), np.asarray(y_chunk),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_chunk),
                               rtol=1e-3, atol=1e-3)
