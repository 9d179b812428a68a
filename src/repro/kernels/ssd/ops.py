"""jit'd public wrapper for the SSD kernel: broadcasts groups to heads,
pads S to a chunk multiple, returns (y, final_state)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..backend import interpret_mode
from .ssd import ssd_pallas


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, dt, A, B, C, D, *, chunk: int = 128, interpret: bool | None = None):
    """x [b,S,H,P]; dt [b,S,H]; A,D [H]; B,C [b,S,G,N] with G | H.
    Returns (y [b,S,H,P], h_final [b,H,P,N]).

    Note: h_final is recomputed with the jnp reference recurrence (cheap,
    O(S/C) chunk reductions) because the kernel's scratch state is not an
    output; serving paths that need the state use models/ssm directly.
    """
    if interpret is None:
        interpret = interpret_mode()
    b, S, H, P = x.shape
    G = B.shape[2]
    rep = H // G
    Bh = jnp.repeat(B, rep, axis=2)
    Ch = jnp.repeat(C, rep, axis=2)
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bh = jnp.pad(Bh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Ch = jnp.pad(Ch, ((0, 0), (0, pad), (0, 0), (0, 0)))
    y = ssd_pallas(x, dt, A, Bh, Ch, D, chunk=chunk, interpret=interpret)

    # final state via the chunk recurrence (matches ssd_reference)
    from repro.models.ssm import ssd_reference
    _, h_final = ssd_reference(x[:, :S], dt[:, :S], A, Bh[:, :S],
                               Ch[:, :S], D, chunk)
    return y[:, :S], h_final
