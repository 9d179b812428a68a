"""Float32 building blocks shared by the plain references in refs/.

Everything here runs in float32 at `Precision.HIGHEST`. `quant="fp8"`
turns a projection into the control: weights rounded to float8 e4m3 per
output column and activations per row (each scaled so that its largest
magnitude is e4m3's largest, 448), then multiplied in float32 -- the
8-bit path a later change might be tempted to serve. The rounding is
written out in float32 arithmetic, so it needs no float8 support from the
chip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


E4M3_MAX = 448.0


def _e4m3(y):
    """Round to the nearest float8 e4m3 value (3 mantissa bits, normal
    exponents down to -6, subnormal steps of 2**-9), saturating at 448."""
    ax = jnp.abs(y)
    e = jnp.floor(jnp.log2(jnp.maximum(ax, 2.0 ** -6)))
    ulp = jnp.exp2(e - 3)
    q = jnp.minimum(jnp.round(ax / ulp) * ulp, E4M3_MAX)
    return jnp.sign(y) * q


def _fake_fp8(a, axes):
    scale = jnp.max(jnp.abs(a), axis=axes, keepdims=True) / E4M3_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return _e4m3(a / scale) * scale


def mm(x, w, n_contract: int = 1, quant: str | None = None):
    """x [..., c1..cn] @ w [c1..cn, ...]: contracts the last `n_contract`
    axes of x with the first `n_contract` axes of w, in float32."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x = _fake_fp8(x, tuple(range(x.ndim - n_contract, x.ndim)))
        w = _fake_fp8(w, tuple(range(n_contract)))
    elif quant is not None:
        raise ValueError(f"unknown quantisation {quant!r}")
    return jnp.tensordot(x, w, axes=n_contract, precision=HIGHEST)


def rmsnorm(x, w, eps: float):
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def layer_slice(stacked, i):
    """Layer `i` of a stacked parameter tree, upcast to float32."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False
                                               ).astype(jnp.float32),
        stacked)
