"""Pallas TPU kernel: weight-stationary tiled GEMM — the SOSA pod.

TPU-native adaptation of the paper's systolic pod (DESIGN.md §2):

  * the (bm x bk x bn) VMEM block is the "pod array": weights stay resident
    in VMEM across the K-walk (weight-stationary), activations stream
    through, int32 partial sums accumulate in a VMEM scratch — the TPU
    analogue of the paper's psum-through-the-column flow;
  * the grid is ordered K-minor so the accumulator scratch carries partial
    sums across K steps exactly like the paper's psum chaining (§4.2);
  * the paper's SIMD post-processor (Fig 7) becomes the fused epilogue:
    dequant scale + bias + activation run in-kernel on the final K step,
    saving one full HBM round-trip of the output;
  * dtypes follow §5: int8 activations x int8 weights -> int32 accumulate
    (TPU MXU has no int16 accumulator; strictly wider than the paper's
    int16 psums) with an f32 dequant epilogue. A bf16 x bf16 -> f32 path
    serves the training stack.

Block shapes are the kernel-level output of the SOSA granularity DSE: lane
dims must be multiples of 128 (MXU), sublane multiples of 8/32.

Autotuner contract (parallel.autoshard.choose_blocks)
-----------------------------------------------------
Block geometry is no longer a static 256^3 default: when the ops.py
wrappers are called without explicit blocks, the DSE cost model picks them.
The mapping between the kernel and the analytical tiling model
(core.tiling.tile_stats) is exact:

  * ``block_k``  = the pod array's contraction rows (ArrayConfig.rows),
  * ``block_n``  = the pod array's output columns  (ArrayConfig.cols),
  * ``block_m``  = the activation rows streamed per tile (``k_part``),

so ``tile_stats([GemmSpec(M, K, N)], ArrayConfig(rows=block_k,
cols=block_n), k_part=block_m)`` returns exactly this kernel's grid counts:
``n_i = M/block_m`` x ``n_l = N/block_n`` x ``n_j = K/block_k`` (the RAW
psum-chain depth carried by the accumulator scratch). `choose_blocks`
scores every candidate geometry with a roofline over those counts —
max(padded-MAC compute, HBM block traffic) — plus the stream's exposed
first and last blocks and a fixed cost per grid step, and rejects
candidates whose VMEM working set (double-buffered x/w and output blocks
+ accumulator + one block of 32-bit temporaries) exceeds the budget
(default 12 MiB of the ~16 MiB scoped VMEM). A dimension's candidates are
the 128-multiples that divide it, so it is never padded (a small-M
decode stream takes blocks as long as K); where none divides it, 128,
256 and 512. Results are lru-cached per (shape, dtype), so the serving
hot loop pays for an autotune once per distinct layer shape.

Stacked weights: `systolic_gemm_pallas(..., layer=i)` takes w as a stack
of all layers' weights, [L, K, N] or [L, K, H, D] (a per-head weight as
stored), and a layer index. The index is a scalar-prefetch operand that
the w BlockSpec puts on the squeezed layer axis, so each grid step DMAs
the layer's block straight from the stack: a layer scan never copies a
layer's weights out first (XLA cannot fuse a slice into a Pallas call's
operand).

The grouped variant (`grouped_systolic_gemm_pallas`) adds a leading
group axis to the grid — G independent (M x K) @ (K x N) problems in one
kernel launch (MoE experts, multi-tenant fused lanes); block geometry and
the psum-chain walk are per-group identical.

The transposed-weight variant (`systolic_gemm_nt_pallas`) contracts
x [M, K] against w stored as [N, K] — out = x @ w.T — streaming w blocks
in their stored layout. This is the tied-embedding unembed shape: the
[vocab, d] token-embedding table serves as the LM head without ever
materializing a [d, vocab] transpose copy in HBM (at nemotron scale that
copy alone is 9.4 GB). The cost model is layout-invariant (same block
bytes, same grid walk), so `choose_blocks` scores it identically.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _accumulate(x, w, acc_ref):
    if x.dtype == jnp.int8:
        acc_ref[...] += jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
    else:
        acc_ref[...] += jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _accumulate_nt(x, w, acc_ref):
    """acc += x [bm, bk] @ w[bn, bk]^T — contraction on the shared K axis,
    w consumed in its stored (transposed) layout."""
    pref = jnp.int32 if x.dtype == jnp.int8 else jnp.float32
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())), preferred_element_type=pref)


def _epilogue_math(acc, scale, bias, activation):
    """The paper's SIMD post-processor: dequant + bias + activation."""
    acc = acc.astype(jnp.float32)
    acc = acc * scale.astype(jnp.float32)                # dequant (per-col)
    acc = acc + bias.astype(jnp.float32)
    if activation == "relu":
        acc = jnp.maximum(acc, 0.0)
    elif activation == "gelu":
        acc = jax.nn.gelu(acc)
    elif activation == "silu":
        acc = acc * jax.nn.sigmoid(acc)
    elif activation == "relu2":
        acc = jnp.square(jnp.maximum(acc, 0.0))
    return acc


def _gemm_kernel(x_ref, w_ref, scale_ref, bias_ref, o_ref, acc_ref, *,
                 n_k: int, activation: str | None, out_dtype):
    """One (i, j, k) grid step: acc += x_blk @ w_blk; epilogue at k == last."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...]
    if w.ndim == 3:                  # [bk, heads, hd] as stored: relayout
        w = w.reshape(w.shape[0], -1)    # in VMEM to the [bk, bn] block
    _accumulate(x_ref[...], w, acc_ref)

    @pl.when(k == n_k - 1)
    def _epilogue():
        o_ref[...] = _epilogue_math(
            acc_ref[...], scale_ref[...], bias_ref[...],
            activation).astype(out_dtype)


def systolic_gemm_pallas(
    x: jax.Array,                  # [M, K] int8 | bf16
    w: jax.Array,                  # [K, N], or a stack [L, K, N] | [L, K, H, D]
    scale: jax.Array,              # [N] f32 dequant scale (ones if None)
    bias: jax.Array,               # [N] f32
    *,
    layer: jax.Array | None = None,   # int32 scalar: the stack's layer
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 256,
    activation: str | None = None,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """out = epilogue((x @ w) * scale + bias).

    N need not be a multiple of block_n: the last block along N then
    overhangs it, its columns past N are garbage on the way in and are
    dropped on the way out, and every column that is kept contracts
    only in-range data (K is whole blocks).

    With `layer`, w is a stack of layers and the kernel reads layer
    `layer`'s blocks straight from it: the index is a scalar-prefetch
    operand that the w BlockSpec's index_map puts on the (squeezed)
    layer axis, so no slice of the stack is ever copied out. A stack
    [L, K, H, D] holds each layer's weight as stored for heads
    ([d, heads, head_dim], N = H x D): its blocks span block_n // D whole
    heads, and the kernel folds each block to [block_k, block_n] in VMEM,
    so the [K, N] view never exists in HBM."""
    M, K = x.shape
    K2 = w.shape[0 if layer is None else 1]
    N = math.prod(w.shape[1 if layer is None else 2:])
    assert K == K2 and w.ndim in ((2,) if layer is None else (3, 4))
    assert M % block_m == 0 and K % block_k == 0, (
        "caller (ops.py) pads M and K to block multiples")
    n_k = K // block_k
    grid = (M // block_m, pl.cdiv(N, block_n), n_k)

    kernel = functools.partial(
        _gemm_kernel, n_k=n_k, activation=activation, out_dtype=out_dtype)
    acc_dtype = jnp.int32 if x.dtype == jnp.int8 else jnp.float32
    # index maps take the scalar-prefetch ref (if any) as a trailing arg
    if layer is None:
        w_spec = pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j))
    elif w.ndim == 3:
        w_spec = pl.BlockSpec((None, block_k, block_n),
                              lambda i, j, k, lyr: (lyr[0], k, j))
    else:
        D = w.shape[3]
        assert block_n % D == 0
        w_spec = pl.BlockSpec((None, block_k, block_n // D, D),
                              lambda i, j, k, lyr: (lyr[0], k, j, 0))
    spec = dict(
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k, *_: (i, k)),
            w_spec,
            pl.BlockSpec((1, block_n), lambda i, j, k, *_: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k, *_: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, j, k, *_: (i, j)),
        scratch_shapes=[
            # int32/f32 accumulator = the pod's psum registers
            pltpu.VMEM((block_m, block_n), acc_dtype),
        ],
    )
    args = (x, w, scale.reshape(1, N), bias.reshape(1, N))
    if layer is None:
        grid_spec = pl.GridSpec(**spec)
    else:
        grid_spec = pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=1,
                                                 **spec)
        kernel = functools.partial(_skip_prefetch, kernel)
        args = (jnp.reshape(layer, (1,)).astype(jnp.int32),) + args
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        interpret=interpret,
    )(*args)


def _skip_prefetch(kernel, layer_ref, *refs):
    """The kernel body of a stacked call: the layer index only steers the
    w BlockSpec's DMAs, so the body never reads it."""
    del layer_ref
    kernel(*refs)


def _grouped_gemm_kernel(x_ref, w_ref, scale_ref, bias_ref, o_ref, acc_ref,
                         *, n_k: int, activation: str | None, out_dtype):
    """One (g, i, j, k) grid step of G independent GEMMs (K-minor walk)."""
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _accumulate(x_ref[0], w_ref[0], acc_ref)

    @pl.when(k == n_k - 1)
    def _epilogue():
        o_ref[0] = _epilogue_math(
            acc_ref[...], scale_ref[0], bias_ref[0],
            activation).astype(out_dtype)


def grouped_systolic_gemm_pallas(
    x: jax.Array,                  # [G, M, K] int8 | bf16
    w: jax.Array,                  # [G, K, N]
    scale: jax.Array,              # [G, N] f32 per-group dequant scale
    bias: jax.Array,               # [G, N] f32
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 256,
    activation: str | None = None,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """G independent pods in one launch: grid grows a leading group axis,
    every group walks its own K-minor psum chain through the shared
    accumulator scratch (groups are grid-major, so the scratch is reused
    group after group exactly as it is tile after tile)."""
    G, M, K = x.shape
    G2, K2, N = w.shape
    assert G == G2 and K == K2
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0, (
        "caller (ops.py) pads to block multiples")
    n_k = K // block_k
    grid = (G, M // block_m, N // block_n, n_k)

    kernel = functools.partial(
        _grouped_gemm_kernel, n_k=n_k, activation=activation,
        out_dtype=out_dtype)
    acc_dtype = jnp.int32 if x.dtype == jnp.int8 else jnp.float32
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_m, block_k), lambda g, i, j, k: (g, i, k)),
            pl.BlockSpec((1, block_k, block_n), lambda g, i, j, k: (g, k, j)),
            pl.BlockSpec((1, 1, block_n), lambda g, i, j, k: (g, 0, j)),
            pl.BlockSpec((1, 1, block_n), lambda g, i, j, k: (g, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_m, block_n),
                               lambda g, i, j, k: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((G, M, N), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((block_m, block_n), acc_dtype),
        ],
        interpret=interpret,
    )(x, w, scale.reshape(G, 1, N), bias.reshape(G, 1, N))


def _gemm_nt_kernel(x_ref, w_ref, scale_ref, bias_ref, o_ref, acc_ref, *,
                    n_k: int, activation: str | None, out_dtype):
    """One (i, j, k) grid step of the transposed-weight walk:
    acc += x_blk @ w_blk^T; epilogue at k == last."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _accumulate_nt(x_ref[...], w_ref[...], acc_ref)

    @pl.when(k == n_k - 1)
    def _epilogue():
        o_ref[...] = _epilogue_math(
            acc_ref[...], scale_ref[...], bias_ref[...],
            activation).astype(out_dtype)


def systolic_gemm_nt_pallas(
    x: jax.Array,                  # [M, K] int8 | bf16
    w: jax.Array,                  # [N, K] — stored transposed (tied embed)
    scale: jax.Array,              # [N] f32 dequant scale (ones if None)
    bias: jax.Array,               # [N] f32
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 256,
    activation: str | None = None,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """out = epilogue((x @ w.T) * scale + bias) with w in [N, K] layout.
    Same K-minor psum-chain grid as `systolic_gemm_pallas`, the last
    block along N overhanging N as there; only the w BlockSpec walks
    (j, k) instead of (k, j)."""
    M, K = x.shape
    N, K2 = w.shape
    assert K == K2
    assert M % block_m == 0 and K % block_k == 0, (
        "caller (ops.py) pads M and K to block multiples")
    n_k = K // block_k
    grid = (M // block_m, pl.cdiv(N, block_n), n_k)

    kernel = functools.partial(
        _gemm_nt_kernel, n_k=n_k, activation=activation, out_dtype=out_dtype)
    acc_dtype = jnp.int32 if x.dtype == jnp.int8 else jnp.float32
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_n, block_k), lambda i, j, k: (j, k)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((block_m, block_n), acc_dtype),
        ],
        interpret=interpret,
    )(x, w, scale.reshape(1, N), bias.reshape(1, N))
