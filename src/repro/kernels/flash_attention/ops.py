"""jit'd public wrapper: pads sequence dims to block multiples and masks
the padded KV tail via kv_len."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..backend import interpret_mode
from .flash_attention import flash_attention_pallas


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softmax_scale", "block_q",
                     "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    softmax_scale: float | None = None, block_q: int = 512,
                    block_k: int = 512, interpret: bool | None = None):
    if interpret is None:
        interpret = interpret_mode()
    B, Sq, Hq, D = q.shape
    Skv = k.shape[1]
    bq = min(block_q, max(8, Sq))
    bk = min(block_k, max(8, Skv))
    pq = (-Sq) % bq
    pk = (-Skv) % bk
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0))) if pq else q
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else k
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else v
    out = flash_attention_pallas(
        qp, kp, vp, causal=causal, window=window,
        softmax_scale=softmax_scale, block_q=bq, block_k=bk,
        kv_len=Skv, interpret=interpret)
    return out[:, :Sq]
