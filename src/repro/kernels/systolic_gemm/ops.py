"""jit'd public wrappers for the systolic GEMM kernels: pad to block
multiples, dispatch to Pallas (Mosaic on TPU, interpret mode on the CPU
backend: kernels/backend.py), slice back. A weight is never padded along
N under a lane-aligned block: the last block overhangs N and the kernel
discards what lies past it (`_overhangs`), so a weight such as
mamba2's [1024, 4384] in_proj or a [50288, 1024] tied embedding is read
in place.

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
import math

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


def _auto_blocks(m: int, k: int, n: int, dtype, out_dtype, head_dim: int = 0
                 ) -> tuple[int, int, int]:
    """DSE-tuned block geometry (lazy import keeps kernels importable
    without the parallel/ package and avoids a module cycle)."""
    from ...parallel.autoshard import choose_blocks
    return choose_blocks(m, k, n,
                         dtype_bytes=jnp.dtype(dtype).itemsize,
                         out_bytes=jnp.dtype(out_dtype).itemsize,
                         head_dim=head_dim)


def _overhangs(n: int, bn: int) -> bool:
    """Whether the kernel's last block along N may overhang N, so that w
    is read in place instead of padded: a lane-aligned block does (the
    kernel discards the out-of-range columns), a narrower one pads N."""
    return bn % 128 == 0 or n % bn == 0


def _streams(w, n: int, blocks: tuple[int, int, int]) -> bool:
    """Whether the kernel can read a stack's layer in place at these
    (clipped) blocks: they divide K (a stack is never padded) and divide
    N or overhang it (`_overhangs`), and a stack stored as [L, K, H, D]
    has lane-wide heads and blocks of whole groups of heads
    (autoshard.head_blocks)."""
    from ...parallel.autoshard import head_blocks
    _, bn, bk = blocks
    if w.shape[1] % bk or not _overhangs(n, bn):
        return False
    if w.ndim == 3:
        return True
    d = w.shape[3]
    return d % 128 == 0 and bn in head_blocks(
        n, d, jnp.dtype(w.dtype).itemsize, (bn,))


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
def systolic_gemm(x, w, scale=None, bias=None, *, layer=None,
                  activation=None,
                  block_m: int | None = None, block_n: int | None = None,
                  block_k: int | None = None,
                  out_dtype=jnp.float32, interpret: bool | None = None):
    """out = epilogue((x @ w) * scale + bias). x [M,K], w [K,N].

    int8 x int8 -> int32 accumulate; bf16/f32 -> f32 accumulate.
    The fused epilogue is the paper's SIMD post-processor (DESIGN.md §2).
    Blocks default to the tile_stats autotuner (choose_blocks).

    With `layer` (an int32 scalar, traced or not), w is a layer stack
    [L, K, N], or [L, K, H, D] for a weight stored per head (N = H x D),
    and the product uses w[layer]: the kernel streams that layer's blocks
    from the stack, which is never padded or relayouted (either would
    copy all of it). Where the blocks cannot stream (`_streams`), the
    layer is sliced out and takes the per-layer path.
    """
    if interpret is None:
        interpret = interpret_mode()
    M, K = x.shape
    N = math.prod(w.shape[1 if layer is None else 2:])
    if w.ndim == 4 and w.shape[2] == 1:      # one head: drop its unit axis
        w = w.reshape(w.shape[:2] + w.shape[3:])
    head_dim = w.shape[3] if w.ndim == 4 else 0
    if block_m is None or block_n is None or block_k is None:
        am, an, ak = _auto_blocks(M, K, N, x.dtype, out_dtype, head_dim)
        block_m, block_n, block_k = (block_m or am, block_n or an,
                                     block_k or ak)
    bm, bn, bk = (min(block_m, _rup(M)), min(block_n, _rup(N)),
                  min(block_k, _rup(K)))
    if layer is not None and not _streams(w, N, (bm, bn, bk)):
        w = jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)
        w = w.reshape(K, N)
        layer = None
    xp = _pad_to(_pad_to(x, bm, 0), bk, 1)
    pn = 1 if _overhangs(N, bn) else bn
    wp = w if layer is not None else _pad_to(_pad_to(w, bk, 0), pn, 1)
    if scale is None:
        scale = jnp.ones((N,), jnp.float32)
    if bias is None:
        bias = jnp.zeros((N,), jnp.float32)
    sp = _pad_to(scale, pn, 0)
    bp = _pad_to(bias, pn, 0)
    out = systolic_gemm_pallas(
        xp, wp, sp, bp, layer=layer, block_m=bm, block_n=bn, block_k=bk,
        activation=activation, out_dtype=out_dtype, interpret=interpret)
    return out[:M, :N]


def fused_lane_gemm(x, w, scale=None, bias=None, *, activation=None,
                    out_dtype=None, interpret: bool | None = None,
                    block_m: int | None = None, block_n: int | None = None,
                    block_k: int | None = None, guard=None, layer=None):
    """Fused-lane GEMM: x [..., K] @ w [K, N] -> [..., N]; with `layer`,
    w is a stack [L, K, N] or [L, K, H, D] read in place (see
    `systolic_gemm`).

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
        if layer is not None:
            w = jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)
            w = w.reshape(w.shape[0], -1)
        out = guarded_gemm(
            x.reshape(m, x.shape[-1]), w, scale, bias, guard=guard,
            activation=activation, out_dtype=out_dtype, interpret=interpret)
    else:
        out = systolic_gemm(
            x.reshape(m, x.shape[-1]), w, scale, bias, layer=layer,
            activation=activation,
            block_m=block_m, block_n=block_n, block_k=block_k,
            out_dtype=out_dtype, interpret=interpret)
    return out.reshape(lead + (out.shape[-1],))


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
    pn = 1 if _overhangs(N, bn) else bn
    wp = _pad_to(_pad_to(w, pn, 0), bk, 1)
    if scale is None:
        scale = jnp.ones((N,), jnp.float32)
    if bias is None:
        bias = jnp.zeros((N,), jnp.float32)
    sp = _pad_to(scale, pn, 0)
    bp = _pad_to(bias, pn, 0)
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
