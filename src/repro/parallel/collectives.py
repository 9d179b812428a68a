"""Butterfly collectives — the paper's interconnect as a collective schedule.

SOSA's Butterfly network (§3.2, Fig 6) is a log2(N)-stage fabric where stage
t connects nodes differing in bit t. The distributed-training analogue is
the recursive-halving/doubling ("butterfly") all-reduce: log2(N) rounds of
pairwise exchange at doubling distances — the exact communication DAG of
Fig 6, expressed with shard_map + jax.lax.ppermute.

On a TPU torus XLA defaults to ring reductions (bandwidth-optimal for large
payloads: 2·(N-1)/N·bytes at N-1 latency hops). The butterfly schedule
moves the same total bytes in log2(N) rounds — latency-optimal for the
small/medium reductions SOSA targets (many small pods => many small
tensors). benchmarks/interconnect.py reports the crossover; the expansion-2
variant splits the payload over two disjoint plane schedules per round
(dual-ring analogue) like the paper's Butterfly-2.

All variants are exact (bit-reproducible vs jnp.sum ordering differences
bounded by fp associativity) and validated in tests/test_collectives.py.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def butterfly_all_reduce(x: jax.Array, axis_name: str) -> jax.Array:
    """Recursive-doubling all-reduce: log2(N) ppermute rounds (Fig 6 DAG).

    Round t exchanges with the partner differing in bit t of the axis
    index; after all rounds every shard holds the full sum.
    """
    n = jax.lax.axis_size(axis_name)
    assert n & (n - 1) == 0, "butterfly all-reduce needs power-of-two axis"
    rounds = int(math.log2(n))
    idx = jax.lax.axis_index(axis_name)
    for t in range(rounds):
        bit = 1 << t
        partner_perm = [(i, i ^ bit) for i in range(n)]
        other = jax.lax.ppermute(x, axis_name, partner_perm)
        x = x + other
    return x


def butterfly_all_reduce_expansion2(x: jax.Array, axis_name: str) -> jax.Array:
    """Butterfly-2: split the payload in half and run the two halves on
    plane-0 (LSB-first) and plane-1 (MSB-first) schedules — disjoint link
    sets per round, doubling effective injection bandwidth (the paper's
    expansion argument)."""
    n = jax.lax.axis_size(axis_name)
    assert n & (n - 1) == 0
    rounds = int(math.log2(n))
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % 2
    if pad:
        flat = jnp.pad(flat, (0, pad))
    a, b = jnp.split(flat, 2)
    for t in range(rounds):
        bit_a = 1 << t                      # plane 0: LSB-first
        bit_b = 1 << (rounds - 1 - t)       # plane 1: MSB-first
        a = a + jax.lax.ppermute(a, axis_name,
                                 [(i, i ^ bit_a) for i in range(n)])
        b = b + jax.lax.ppermute(b, axis_name,
                                 [(i, i ^ bit_b) for i in range(n)])
    out = jnp.concatenate([a, b])
    if pad:
        out = out[:-pad]
    return out.reshape(x.shape)


def butterfly_reduce_scatter(x: jax.Array, axis_name: str) -> jax.Array:
    """Recursive-halving reduce-scatter: log2(N) rounds, halving payload
    each round; shard i ends with the i-th 1/N slice of the sum.
    x's leading dim must be divisible by N."""
    n = jax.lax.axis_size(axis_name)
    assert n & (n - 1) == 0
    rounds = int(math.log2(n))
    idx = jax.lax.axis_index(axis_name)
    buf = x
    # walk bits MSB -> LSB: exchange the half we don't keep
    for t in range(rounds - 1, -1, -1):
        bit = 1 << t
        half = buf.shape[0] // 2
        lo, hi = buf[:half], buf[half:]
        has_bit = (idx & bit) != 0
        # keep the half matching our bit, ship the other to the partner
        keep = jax.lax.cond(has_bit, lambda: hi, lambda: lo)
        ship = jax.lax.cond(has_bit, lambda: lo, lambda: hi)
        other = jax.lax.ppermute(ship, axis_name,
                                 [(i, i ^ bit) for i in range(n)])
        buf = keep + other
    return buf


def butterfly_all_gather(x: jax.Array, axis_name: str) -> jax.Array:
    """Recursive-doubling all-gather (inverse of the reduce-scatter walk).

    Note: the gathered order is bit-reversal-composed; paired with
    `butterfly_reduce_scatter` (same bit walk) the composition
    all_gather(reduce_scatter(x)) == all_reduce(x) holds exactly, which is
    the only way we use it (ZeRO-1 gradient path)."""
    n = jax.lax.axis_size(axis_name)
    assert n & (n - 1) == 0
    rounds = int(math.log2(n))
    idx = jax.lax.axis_index(axis_name)
    buf = x
    for t in range(rounds):
        bit = 1 << t
        other = jax.lax.ppermute(buf, axis_name,
                                 [(i, i ^ bit) for i in range(n)])
        has_bit = (idx & bit) != 0
        lo = jax.lax.cond(has_bit, lambda: other, lambda: buf)
        hi = jax.lax.cond(has_bit, lambda: buf, lambda: other)
        buf = jnp.concatenate([lo, hi], axis=0)
    return buf


def ring_all_reduce(x: jax.Array, axis_name: str) -> jax.Array:
    """Baseline: 2(N-1)-step ring (reduce-scatter + all-gather), the
    torus-native schedule XLA favors — SOSA's mesh/H-tree analogue."""
    n = jax.lax.axis_size(axis_name)
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(n, -1)
    idx = jax.lax.axis_index(axis_name)
    ring = [(i, (i + 1) % n) for i in range(n)]

    # reduce-scatter: start with own chunk (idx+1); at step s, the incoming
    # partial is for chunk (idx - s) mod n — add our copy of it and pass on.
    acc = jnp.take(chunks, (idx + 1) % n, axis=0)
    for step in range(n - 1):
        acc = jax.lax.ppermute(acc, axis_name, ring)
        slot = (idx - step) % n
        acc = acc + jnp.take(chunks, slot, axis=0)
    # node idx now owns the fully reduced chunk (idx+2) mod n; all-gather
    out = [acc]
    cur = acc
    for step in range(n - 1):
        cur = jax.lax.ppermute(cur, axis_name, ring)
        out.append(cur)
    # out[k] came from node (idx - k): it owns chunk (idx - k + 2) mod n
    stacked = jnp.stack(out)                    # [n, chunk]
    owners = (idx + 2 - jnp.arange(n)) % n
    ordered = jnp.zeros_like(stacked).at[owners].set(stacked)
    flat_out = ordered.reshape(-1)
    if pad:
        flat_out = flat_out[:-pad]
    return flat_out.reshape(x.shape)


COLLECTIVES = {
    "psum": lambda x, ax: jax.lax.psum(x, ax),
    "butterfly": butterfly_all_reduce,
    "butterfly2": butterfly_all_reduce_expansion2,
    "ring": ring_all_reduce,
}


def all_reduce_under_mesh(mesh: Mesh, axis_name: str, impl: str = "butterfly"):
    """shard_map-wrapped all-reduce over one mesh axis for replicated use:
    f(x sharded on axis) -> x summed, replicated on that axis."""
    fn = COLLECTIVES[impl]
    spec_in = P(axis_name)
    spec_out = P(axis_name)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=spec_in,
                       out_specs=spec_out, check_vma=False)
    def _run(x):
        return fn(x, axis_name)

    return _run
