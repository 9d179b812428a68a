"""Common layers + the param-schema system.

Every model declares a *schema*: a pytree (nested dicts) of `ParamSpec`s,
each carrying shape, dtype, init style and **logical axis names**. From one
schema we derive three synchronized views:

  * `init_from_schema`    — materialized parameters (random init),
  * `shapes_from_schema`  — jax.ShapeDtypeStruct stand-ins (dry-run: no
                            allocation, exactly the shannon/kernels pattern),
  * `parallel.sharding.pspecs_from_schema` — PartitionSpecs via logical-axis
                            rules with divisibility guards.

Models are pure functions over these param trees (no flax); layer stacks
carry a leading "layers" axis and are scanned with jax.lax.scan so the
lowered HLO is O(1) in depth — essential for compiling 96-layer/340B
configs on the CPU dry-run host.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]          # logical axis names, len == ndim
    init: str = "normal"                  # normal | zeros | ones
    scale: float | None = None            # stddev; default fan-in
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


@functools.partial(jax.jit, static_argnums=1)
def _init_leaf(key, spec: ParamSpec):
    """One leaf, as one program: the float32 draw, then the scale fused
    with the cast, so only the draw and the leaf's own dtype are ever on
    the device (an eager `(draw * scale).astype` also holds the float32
    product). The barrier keeps the draw the same program as an eager
    `jax.random.normal`: fusing the scale into it changes float32 bits."""
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    scale = spec.scale
    if scale is None:
        fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
        scale = 1.0 / math.sqrt(max(1, fan_in))
    draw = jax.lax.optimization_barrier(
        jax.random.normal(key, spec.shape, jnp.float32))
    return (draw * scale).astype(spec.dtype)


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def init_from_schema(rng, schema):
    """Random params: leaf i draws from the i-th split of `rng` in
    flattened order. Leaves are made largest first, while the device is
    emptiest, which changes no value: a full-width stack of [L, d, d_ff]
    leaves would not find room for its float32 draw last."""
    leaves, treedef = jax.tree_util.tree_flatten(schema, is_leaf=is_spec)
    keys = jax.random.split(rng, len(leaves))
    vals = [None] * len(leaves)
    for i in sorted(range(len(leaves)),
                    key=lambda i: -math.prod(leaves[i].shape)):
        vals[i] = _init_leaf(keys[i], leaves[i])
    return jax.tree_util.tree_unflatten(treedef, vals)


def shapes_from_schema(schema):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), schema,
        is_leaf=is_spec)


def param_count(schema) -> int:
    leaves = jax.tree_util.tree_leaves(schema, is_leaf=is_spec)
    return sum(math.prod(s.shape) for s in leaves)


# --------------------------------------------------------------------------
# primitive layers (pure functions over param dicts)
# --------------------------------------------------------------------------

def rmsnorm(x, w, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def layernorm(x, w, b, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(x.dtype) * w + b


def norm_schema(d: int, kind: str) -> dict:
    if kind == "layernorm":
        return {"scale": ParamSpec((d,), ("embed",), init="ones"),
                "bias": ParamSpec((d,), ("embed",), init="zeros")}
    return {"scale": ParamSpec((d,), ("embed",), init="ones")}


def apply_norm(p: dict, x, kind: str):
    if kind == "layernorm":
        return layernorm(x, p["scale"], p["bias"])
    return rmsnorm(x, p["scale"])


def rope_freqs(head_dim: int, theta: float):
    return theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)


def apply_rope(x, positions, theta: float):
    """x: [..., S, H, hd]; positions: broadcastable to [..., S]."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # [hd/2]
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    cos = jnp.cos(ang)[..., :, None, :]                 # [..., S, 1, hd/2]
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def pod_dense(x, w, *, activation: str | None = None):
    """One dense projection on the Pallas systolic pod GEMM.

    Fused-lane execution: every leading axis of x (decode lanes, sequence,
    batch) collapses into the GEMM M axis, so a decode batch's per-lane
    GEMVs run as the ONE fused [lanes, K] @ [K, N] GEMM the tenancy
    co-scheduling analysis assumes. Trailing axes of w beyond the
    contraction fold into N and unfold on return (e.g. [d, H, hd] heads).
    Block geometry comes from the DSE autotuner
    (parallel.autoshard.choose_blocks, per-shape cached); `activation`
    runs in the kernel's fused epilogue (the paper's SIMD post-processor).
    """
    from ..kernels.systolic_gemm.guard import active_guard
    from ..kernels.systolic_gemm.ops import fused_lane_gemm
    k = x.shape[-1]
    out = fused_lane_gemm(x, w.reshape(k, -1), activation=activation,
                          out_dtype=x.dtype, guard=active_guard())
    return out.reshape(x.shape[:-1] + w.shape[1:])


def activation_fn(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return jax.nn.gelu
    if name == "relu2":
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(name)


def mlp_schema(d_model: int, d_ff: int, activation: str,
               layers: int | None = None) -> dict:
    """Gated (GLU) for silu/gelu-glu archs; plain up/down for relu2/gelu."""
    lead = (layers,) if layers else ()
    lax_ = ("layers",) if layers else ()
    gated = activation in ("silu",)
    sch = {
        "up": ParamSpec(lead + (d_model, d_ff), lax_ + ("embed", "ff")),
        "down": ParamSpec(lead + (d_ff, d_model), lax_ + ("ff", "embed")),
    }
    if gated:
        sch["gate"] = ParamSpec(lead + (d_model, d_ff), lax_ + ("embed", "ff"))
    return sch


def apply_mlp(p: dict, x, activation: str, use_pallas: bool = False):
    if use_pallas:
        # activation fuses into the GEMM epilogue (no extra HBM round-trip)
        up = pod_dense(x, p["up"],
                       activation=None if "gate" in p else activation)
        if "gate" in p:
            up = pod_dense(x, p["gate"], activation=activation) * up
        return pod_dense(up, p["down"])
    act = activation_fn(activation)
    up = jnp.einsum("...d,df->...f", x, p["up"])
    if "gate" in p:
        up = act(jnp.einsum("...d,df->...f", x, p["gate"])) * up
    else:
        up = act(up)
    return jnp.einsum("...f,fd->...d", up, p["down"])


def embed_schema(vocab: int, d_model: int, tie: bool) -> dict:
    sch = {"tok": ParamSpec((vocab, d_model), ("vocab", "embed"), scale=1.0)}
    if not tie:
        sch["unembed"] = ParamSpec((d_model, vocab), ("embed", "vocab"))
    return sch


def embed(p: dict, tokens):
    return jnp.take(p["tok"], tokens, axis=0)


def unembed(p: dict, x, use_pallas: bool = False):
    """Hidden states -> logits: the largest single GEMM of the decode step.
    use_pallas routes it through the pod kernel — untied [d, vocab] weights
    on the fused-lane GEMM, tied embeddings on the transposed-weight
    variant, which streams the stored [vocab, d] token table directly (no
    transpose copy of the embedding in HBM)."""
    if use_pallas:
        from ..kernels.systolic_gemm.guard import active_guard
        from ..kernels.systolic_gemm.ops import (fused_lane_gemm,
                                                 fused_lane_gemm_t)
        g = active_guard()
        if "unembed" in p:
            return fused_lane_gemm(x, p["unembed"], out_dtype=x.dtype,
                                   guard=g)
        return fused_lane_gemm_t(x, p["tok"], out_dtype=x.dtype, guard=g)
    if "unembed" in p:
        return jnp.einsum("...d,dv->...v", x, p["unembed"])
    return jnp.einsum("...d,vd->...v", x, p["tok"])


def cross_entropy_loss(logits, labels, ignore_id: int = -1):
    """Stable CE; logits may be vocab-sharded (XLA reduces across shards)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(
        logits, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    mask = (labels != ignore_id).astype(jnp.float32)
    loss = (lse - ll) * mask
    return loss.sum() / jnp.maximum(mask.sum(), 1.0)
