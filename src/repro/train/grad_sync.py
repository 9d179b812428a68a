"""Cross-pod gradient synchronization — the paper's interconnect pillar as
a first-class training feature.

On the multi-pod mesh only data-parallel gradient sums cross the `pod`
axis (DCN-grade links). This module provides drop-in reducers for that
axis, selectable per deployment:

    "psum"        — XLA default (torus-optimal rings on ICI; baseline)
    "butterfly"   — log2(N)-round recursive doubling (parallel/collectives):
                    latency-optimal for the many *small* tensors a
                    SOSA-granularity fleet produces (the paper's Butterfly
                    argument transplanted to collectives)
    "compressed"  — int8 block-quantized psum with error feedback
                    (parallel/compression): 4x fewer bytes on the slowest
                    links; the error-feedback state rides in the optimizer
                    carry so compressed SGD stays unbiased across steps

All reducers run under shard_map over the reduction axis and are
numerically validated against plain psum in tests/test_grad_sync.py.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.collectives import (butterfly_all_reduce,
                                    butterfly_all_reduce_expansion2)
from ..parallel.compression import compressed_psum


def _flatten_grads(grads):
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    shapes = [(l.shape, l.dtype, l.size) for l in leaves]
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32)
                            for l in leaves])
    return flat, shapes, treedef


def _unflatten_grads(flat, shapes, treedef):
    out = []
    off = 0
    for shape, dtype, size in shapes:
        out.append(flat[off:off + size].reshape(shape).astype(dtype))
        off += size
    return jax.tree_util.tree_unflatten(treedef, out)


def make_grad_sync(mesh: Mesh, axis: str = "pod", impl: str = "psum"):
    """Returns sync(grads, error) -> (reduced_grads, new_error).

    grads must be replicated along `axis` up to the missing sum (i.e. each
    pod holds its local-batch gradient). The flattened gradient vector is
    REPLICATED on every device while reducing (P(None) specs): sharding it
    over the non-reduction axes miscompiles on jax<=0.4.37 (see the spec
    comment below), so each device temporarily materializes the full fp32
    flat vector — budget memory accordingly on large models.
    `error` is the error-feedback carry for "compressed" (None otherwise).
    """
    if axis not in mesh.shape:
        return lambda grads, error=None: (grads, error)

    def sync(grads, error=None):
        flat, shapes, treedef = _flatten_grads(grads)

        if impl == "psum":
            def red(x, e):
                return jax.lax.psum(x, axis), e
        elif impl == "butterfly":
            def red(x, e):
                return butterfly_all_reduce(x, axis), e
        elif impl == "butterfly2":
            def red(x, e):
                return butterfly_all_reduce_expansion2(x, axis), e
        elif impl == "compressed":
            def red(x, e):
                r, ne = compressed_psum(x, axis, e)
                return r, ne
        else:
            raise ValueError(impl)

        if error is None and impl == "compressed":
            error = jnp.zeros_like(flat)

        # The reducer sees the full flat vector on every device (P(None)):
        # sharding it over the non-reduction axes (P(other_axes)) miscompiles
        # under jit on jax<=0.4.37 — a concatenate feeding shard_map with
        # check_rep=False loses the pod-replication guarantee and the psum
        # over-reduces (2x/4x too large). Replication is always correct;
        # data-parallel grads are replicated along `axis` by construction.
        spec = P(None)

        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(spec, spec if error is not None else P()),
            out_specs=(spec, spec if error is not None else P()),
            check_vma=False)
        def run(x, e):
            r, ne = red(x, e if error is not None else None)
            return r, (ne if ne is not None else jnp.zeros((), x.dtype))

        # no padding needed: the replicated spec places the whole vector on
        # every device, so there is no shard-divisibility constraint
        red_flat, new_error = run(flat, error if error is not None else
                                  jnp.zeros((), flat.dtype))
        return _unflatten_grads(red_flat, shapes, treedef), \
            (new_error if error is not None else None)

    return sync
