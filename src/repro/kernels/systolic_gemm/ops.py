"""jit'd public wrappers for the systolic GEMM kernels: pad to block
multiples, dispatch to Pallas (Mosaic on TPU, interpret mode on the CPU
backend: kernels/backend.py), slice back.

Block geometry defaults to the DSE autotuner
(parallel.autoshard.choose_blocks — tile_stats-driven, VMEM-budget-aware,
lru-cached per shape; see systolic_gemm.py for the contract). Pass explicit
block_m/n/k to override.

`fused_lane_gemm` is the serving hot-loop entry point: all leading axes of
the activation collapse into the GEMM M axis, so a decode batch's per-lane
GEMVs execute as the ONE fused [lanes, K] @ [K, N] GEMM the multi-tenant
co-scheduling analysis (tenancy/) assumes. `grouped_gemm` runs G
independent GEMMs in one kernel launch (MoE experts / multi-tenant pods).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..backend import interpret_mode
from .systolic_gemm import (grouped_systolic_gemm_pallas,
                            systolic_gemm_nt_pallas, systolic_gemm_pallas)


def _pad_to(x, m, axis):
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _auto_blocks(m: int, k: int, n: int, dtype, out_dtype
                 ) -> tuple[int, int, int]:
    """DSE-tuned block geometry (lazy import keeps kernels importable
    without the parallel/ package and avoids a module cycle)."""
    from ...parallel.autoshard import choose_blocks
    return choose_blocks(m, k, n,
                         dtype_bytes=jnp.dtype(dtype).itemsize,
                         out_bytes=jnp.dtype(out_dtype).itemsize)


def _auto_blocks_grouped(g: int, m: int, k: int, n: int, dtype, out_dtype
                         ) -> tuple[int, int, int]:
    """Grouped-kernel geometry: the per-group problem is what the grid
    tiles, so the autotuner scores (m, k, n) with the group count only
    affecting the (uniform) traffic scale (see choose_blocks_grouped)."""
    from ...parallel.autoshard import choose_blocks_grouped
    return choose_blocks_grouped(g, m, k, n,
                                 dtype_bytes=jnp.dtype(dtype).itemsize,
                                 out_bytes=jnp.dtype(out_dtype).itemsize)


@functools.partial(
    jax.jit,
    static_argnames=("activation", "block_m", "block_n", "block_k",
                     "out_dtype", "interpret"))
def systolic_gemm(x, w, scale=None, bias=None, *, activation=None,
                  block_m: int | None = None, block_n: int | None = None,
                  block_k: int | None = None,
                  out_dtype=jnp.float32, interpret: bool | None = None):
    """out = epilogue((x @ w) * scale + bias). x [M,K], w [K,N].

    int8 x int8 -> int32 accumulate; bf16/f32 -> f32 accumulate.
    The fused epilogue is the paper's SIMD post-processor (DESIGN.md §2).
    Blocks default to the tile_stats autotuner (choose_blocks).
    """
    if interpret is None:
        interpret = interpret_mode()
    M, K = x.shape
    N = w.shape[1]
    if block_m is None or block_n is None or block_k is None:
        am, an, ak = _auto_blocks(M, K, N, x.dtype, out_dtype)
        block_m, block_n, block_k = (block_m or am, block_n or an,
                                     block_k or ak)
    bm, bn, bk = (min(block_m, _rup(M)), min(block_n, _rup(N)),
                  min(block_k, _rup(K)))
    xp = _pad_to(_pad_to(x, bm, 0), bk, 1)
    wp = _pad_to(_pad_to(w, bk, 0), bn, 1)
    if scale is None:
        scale = jnp.ones((N,), jnp.float32)
    if bias is None:
        bias = jnp.zeros((N,), jnp.float32)
    sp = _pad_to(scale, bn, 0)
    bp = _pad_to(bias, bn, 0)
    out = systolic_gemm_pallas(
        xp, wp, sp, bp, block_m=bm, block_n=bn, block_k=bk,
        activation=activation, out_dtype=out_dtype, interpret=interpret)
    return out[:M, :N]


def fused_lane_gemm(x, w, scale=None, bias=None, *, activation=None,
                    out_dtype=None, interpret: bool | None = None,
                    block_m: int | None = None, block_n: int | None = None,
                    block_k: int | None = None, guard=None):
    """Fused-lane GEMM: x [..., K] @ w [K, N] -> [..., N].

    All leading axes of x (decode lanes, sequence positions, batch) fuse
    into the GEMM M axis — one pod GEMM instead of a fan of GEMVs, which
    is exactly the fused-lane shape tenancy/trace.py attributes to the
    engine's step-locked decode. Leading shape is restored on return.

    ``guard`` (a guard.PodGuard, or None) diverts to the SDC-checked
    path (ABFT checksums / Freivalds probe, guard.py); None or mode
    "off" takes the jitted unguarded kernel untouched — bit-identical
    to a build without the guard.
    """
    lead = x.shape[:-1]
    m = 1
    for d in lead:
        m *= d
    out_dtype = jnp.float32 if out_dtype is None else out_dtype
    if guard is not None and guard.mode != "off":
        from .guard import guarded_gemm
        out = guarded_gemm(
            x.reshape(m, x.shape[-1]), w, scale, bias, guard=guard,
            activation=activation, out_dtype=out_dtype, interpret=interpret)
    else:
        out = systolic_gemm(
            x.reshape(m, x.shape[-1]), w, scale, bias, activation=activation,
            block_m=block_m, block_n=block_n, block_k=block_k,
            out_dtype=out_dtype, interpret=interpret)
    return out.reshape(lead + (w.shape[1],))


@functools.partial(
    jax.jit,
    static_argnames=("activation", "block_m", "block_n", "block_k",
                     "out_dtype", "interpret"))
def systolic_gemm_t(x, w, scale=None, bias=None, *, activation=None,
                    block_m: int | None = None, block_n: int | None = None,
                    block_k: int | None = None,
                    out_dtype=jnp.float32, interpret: bool | None = None):
    """out = epilogue((x @ w.T) * scale + bias). x [M,K], w [N,K].

    The transposed-weight pod GEMM: w streams in its stored layout (no
    [K,N] transpose copy) — the tied-embedding unembed runs the [vocab, d]
    token table as the LM head directly. Same autotune/padding contract as
    `systolic_gemm` (the cost model is layout-invariant)."""
    if interpret is None:
        interpret = interpret_mode()
    M, K = x.shape
    N = w.shape[0]
    if block_m is None or block_n is None or block_k is None:
        am, an, ak = _auto_blocks(M, K, N, x.dtype, out_dtype)
        block_m, block_n, block_k = (block_m or am, block_n or an,
                                     block_k or ak)
    bm, bn, bk = (min(block_m, _rup(M)), min(block_n, _rup(N)),
                  min(block_k, _rup(K)))
    xp = _pad_to(_pad_to(x, bm, 0), bk, 1)
    wp = _pad_to(_pad_to(w, bn, 0), bk, 1)
    if scale is None:
        scale = jnp.ones((N,), jnp.float32)
    if bias is None:
        bias = jnp.zeros((N,), jnp.float32)
    sp = _pad_to(scale, bn, 0)
    bp = _pad_to(bias, bn, 0)
    out = systolic_gemm_nt_pallas(
        xp, wp, sp, bp, block_m=bm, block_n=bn, block_k=bk,
        activation=activation, out_dtype=out_dtype, interpret=interpret)
    return out[:M, :N]


def fused_lane_gemm_t(x, w, scale=None, bias=None, *, activation=None,
                      out_dtype=None, interpret: bool | None = None,
                      block_m: int | None = None, block_n: int | None = None,
                      block_k: int | None = None, guard=None):
    """Fused-lane transposed GEMM: x [..., K] @ w [N, K]^T -> [..., N].
    The LM-head entry point: all decode lanes / sequence positions fuse
    into the M axis of ONE pod GEMM against the stored [vocab, d] table.
    ``guard`` as in `fused_lane_gemm` (transposed-layout checksums)."""
    lead = x.shape[:-1]
    m = 1
    for d in lead:
        m *= d
    out_dtype = jnp.float32 if out_dtype is None else out_dtype
    if guard is not None and guard.mode != "off":
        from .guard import guarded_gemm
        out = guarded_gemm(
            x.reshape(m, x.shape[-1]), w, scale, bias, guard=guard,
            activation=activation, out_dtype=out_dtype, transpose=True,
            interpret=interpret)
    else:
        out = systolic_gemm_t(
            x.reshape(m, x.shape[-1]), w, scale, bias, activation=activation,
            block_m=block_m, block_n=block_n, block_k=block_k,
            out_dtype=out_dtype, interpret=interpret)
    return out.reshape(lead + (w.shape[0],))


@functools.partial(
    jax.jit,
    static_argnames=("activation", "block_m", "block_n", "block_k",
                     "out_dtype", "interpret"))
def grouped_gemm(x, w, scale=None, bias=None, *, activation=None,
                 block_m: int | None = None, block_n: int | None = None,
                 block_k: int | None = None,
                 out_dtype=jnp.float32, interpret: bool | None = None):
    """G independent GEMMs in ONE kernel launch: x [G,M,K] @ w [G,K,N]
    -> [G,M,N], with a per-group (scale, bias, activation) epilogue.
    Same padding/autotune contract as `systolic_gemm` (blocks are chosen
    for the per-group (M, K, N) problem)."""
    if interpret is None:
        interpret = interpret_mode()
    G, M, K = x.shape
    N = w.shape[2]
    if block_m is None or block_n is None or block_k is None:
        am, an, ak = _auto_blocks_grouped(G, M, K, N, x.dtype, out_dtype)
        block_m, block_n, block_k = (block_m or am, block_n or an,
                                     block_k or ak)
    bm, bn, bk = (min(block_m, _rup(M)), min(block_n, _rup(N)),
                  min(block_k, _rup(K)))
    xp = _pad_to(_pad_to(x, bm, 1), bk, 2)
    wp = _pad_to(_pad_to(w, bk, 1), bn, 2)
    if scale is None:
        scale = jnp.ones((G, N), jnp.float32)
    if bias is None:
        bias = jnp.zeros((G, N), jnp.float32)
    sp = _pad_to(scale, bn, 1)
    bp = _pad_to(bias, bn, 1)
    out = grouped_systolic_gemm_pallas(
        xp, wp, sp, bp, block_m=bm, block_n=bn, block_k=bk,
        activation=activation, out_dtype=out_dtype, interpret=interpret)
    return out[:, :M, :N]


def _rup(n: int, m: int = 8) -> int:
    """Round up to a multiple of the TPU sublane count."""
    return max(m, ((n + m - 1) // m) * m)
