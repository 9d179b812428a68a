"""Property tests for the tile_stats-driven Pallas block autotuner
(parallel.autoshard.choose_blocks): randomized GemmSpecs — including the
new transposed (tied-embedding LM head, vocab-scale N) and grouped (MoE
per-expert capacity rows) shapes — must yield candidate blocks whose
kernel-effective clipping divides the padded problem and whose VMEM
working set respects the budget."""

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # degrade gracefully: deterministic fallback
    from _hypothesis_fallback import given, settings, st

from repro.parallel.autoshard import (_VMEM_BUDGET, _rup8, block_candidates,
                                      choose_blocks, choose_blocks_grouped)


def _ops_effective(blocks, m, k, n):
    """The kernel-effective geometry, exactly as ops.systolic_gemm clips
    (min(block, sublane-rounded dim)) before padding to block multiples."""
    bm, bn, bk = blocks
    return min(bm, _rup8(m)), min(bn, _rup8(n)), min(bk, _rup8(k))


def _check_contract(blocks, m, k, n, dtype_bytes, out_bytes):
    # each block is a 128-multiple dividing its dimension, or where none
    # divides it, one of the fallback sizes (128, 256, 512)
    for dim, blk in zip((m, n, k), blocks):
        assert blk in block_candidates(dim)
    bm_e, bn_e, bk_e = _ops_effective(blocks, m, k, n)
    # the padded problem ops.py builds is an exact multiple of the
    # effective blocks (the kernel asserts this; here it's a property)
    for dim, blk in ((m, bm_e), (k, bk_e), (n, bn_e)):
        padded = -(-dim // blk) * blk
        assert padded % blk == 0
        assert padded - dim < blk          # never pads a full extra block
    # VMEM working set: double-buffered streaming blocks + accumulator +
    # output block (the same accounting choose_blocks scores with)
    vmem = (2 * (bm_e * bk_e + bk_e * bn_e) * dtype_bytes
            + bm_e * bn_e * (4 + out_bytes))
    assert vmem <= _VMEM_BUDGET, (blocks, (m, k, n), vmem)


@settings(max_examples=30, deadline=None)
@given(m=st.integers(1, 8192), k=st.integers(1, 8192),
       n=st.integers(1, 8192),
       dtype_bytes=st.sampled_from([1, 2, 4]),
       out_bytes=st.sampled_from([2, 4]))
def test_choose_blocks_contract(m, k, n, dtype_bytes, out_bytes):
    blocks = choose_blocks(m, k, n, dtype_bytes=dtype_bytes,
                           out_bytes=out_bytes)
    _check_contract(blocks, m, k, n, dtype_bytes, out_bytes)
    # deterministic (and lru-cached) per shape
    assert blocks == choose_blocks(m, k, n, dtype_bytes=dtype_bytes,
                                   out_bytes=out_bytes)


@settings(max_examples=20, deadline=None)
@given(lanes=st.integers(1, 256), d=st.sampled_from([512, 1024, 4096]),
       vocab=st.integers(1000, 300000))
def test_choose_blocks_transposed_lm_head_shapes(lanes, d, vocab):
    """The unembed GEMM (fused decode lanes x d_model x vocab): the
    transposed-weight kernel scores with the same layout-invariant model,
    so the contract must hold at vocab-scale N (up to nemotron's 256k)."""
    blocks = choose_blocks(lanes, d, vocab, dtype_bytes=2, out_bytes=2)
    _check_contract(blocks, lanes, d, vocab, 2, 2)


@settings(max_examples=20, deadline=None)
@given(g=st.integers(1, 160), cap=st.integers(1, 128),
       d=st.sampled_from([64, 1024, 5120]),
       f=st.sampled_from([32, 1536, 10752]))
def test_choose_blocks_grouped_moe_shapes(g, cap, d, f):
    """Grouped (MoE expert) shapes: G pods of (cap x d x f). The group
    axis scales the roofline uniformly, so the grouped entry point must
    agree with the per-group score and satisfy the same contract."""
    blocks = choose_blocks_grouped(g, cap, d, f)
    _check_contract(blocks, cap, d, f, 2, 4)
    assert blocks == choose_blocks(cap, d, f)


def test_choose_blocks_grouped_rejects_zero_groups():
    with pytest.raises(AssertionError):
        choose_blocks_grouped(0, 8, 64, 64)


def _grid_steps(blocks, m, k, n):
    bm_e, bn_e, bk_e = _ops_effective(blocks, m, k, n)
    return -(-m // bm_e) * -(-n // bn_e) * -(-k // bk_e)


# yi-6b's decode projections at 8 lanes: gate/up, down, q/o, k/v
YI_DECODE_LAYER = [(8, 4096, 11008), (8, 11008, 4096), (8, 4096, 4096),
                   (8, 4096, 512)]


@pytest.mark.parametrize("mkn", YI_DECODE_LAYER,
                         ids=["gate-up", "down", "q-o", "k-v"])
def test_choose_blocks_small_m_takes_few_large_blocks(mkn):
    """A small-M stream is bound by its weight's bytes: with a per-step
    cost in the score the autotuner takes blocks of megabytes, at most 64
    grid steps a projection (1,376 for gate/up with the 128-512 blocks)."""
    m, k, n = mkn
    blocks = choose_blocks(m, k, n, dtype_bytes=2, out_bytes=2)
    _check_contract(blocks, m, k, n, 2, 2)
    assert _grid_steps(blocks, m, k, n) <= 64
    assert n % blocks[1] == 0 and k % blocks[2] == 0     # stack never padded


def test_choose_blocks_lm_head_takes_the_fewest_steps_vmem_allows():
    """yi-6b's LM head ([8, 4096] x [4096, 64000], 524 MB of weight) cannot
    fit 64 steps in the 12 MiB budget: that would take 8 MB blocks, 16 MB
    double-buffered. It takes the fewest steps of any geometry that fits
    (100), against 4,000 with the 128-512 blocks."""
    m, k, n = 8, 4096, 64000
    blocks = choose_blocks(m, k, n, dtype_bytes=2, out_bytes=2)
    _check_contract(blocks, m, k, n, 2, 2)
    fewest = min(
        _grid_steps((8, bn, bk), m, k, n)
        for bn in block_candidates(n) for bk in block_candidates(k)
        if 2 * (8 * bk + bk * bn) * 2 + 8 * bn * 16 <= _VMEM_BUDGET)
    assert _grid_steps(blocks, m, k, n) == fewest == 100


@settings(max_examples=30, deadline=None)
@given(m=st.integers(1, 4096), k=st.integers(1, 96), n=st.integers(1, 96),
       out_bytes=st.sampled_from([2, 4]))
def test_choose_blocks_never_pads_a_dimension_with_a_dividing_block(
        m, k, n, out_bytes):
    """A dimension with a 128-multiple divisor is never padded: M, K and
    N drawn as 128 x (1..96), and M also free (decode lanes)."""
    k, n = 128 * k, 128 * n
    blocks = choose_blocks(m, k, n, dtype_bytes=2, out_bytes=out_bytes)
    _check_contract(blocks, m, k, n, 2, out_bytes)
    bm_e, bn_e, bk_e = _ops_effective(blocks, m, k, n)
    assert k % bk_e == 0 and n % bn_e == 0
    if m % 128 == 0:
        assert m % bm_e == 0


@settings(max_examples=20, deadline=None)
@given(m=st.sampled_from([1, 8, 64, 512, 4096, 8192]),
       k=st.sampled_from([64, 1024, 4096, 5120, 11008, 14336, 28672]),
       n=st.sampled_from([512, 4096, 11008, 50280, 64000, 128256]),
       dtype_bytes=st.sampled_from([1, 2, 4]),
       out_bytes=st.sampled_from([2, 4]))
def test_choose_blocks_contract_over_the_extended_candidates(
        m, k, n, dtype_bytes, out_bytes):
    """The contract (budget, no padding by a full block, sizes from the
    dimension's candidates) over model widths whose divisors reach far
    beyond 512."""
    blocks = choose_blocks(m, k, n, dtype_bytes=dtype_bytes,
                           out_bytes=out_bytes)
    _check_contract(blocks, m, k, n, dtype_bytes, out_bytes)
