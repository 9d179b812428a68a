"""Seeded weights for the served model, drawn by the benchmark itself.

Every leaf is drawn on the device, in the dtype it is served in, in one
jitted call, from the shapes of `jax.eval_shape(model.init)`. The rule is
keyed by the leaf's name (the last key of its path) and does not use the
program's own init, so the yardstick stays fixed when the program's init
changes:

- matrices: normal / sqrt(the dimensions they are contracted over), so
  q/k/v [d, heads, hd] get 1/sqrt(d) and o [heads, hd, d] 1/sqrt(heads*hd).
  Attention logits then have unit scale, and two bf16 paths agree with a
  float32 reference to rounding (a fan-in taken from the heads axis gives
  attention logits in the hundreds, where softmax picks keys by margins
  below bf16 rounding). The same holds for the state-space projections
  (`in_proj`, `out_proj`), latent attention (`q_a`, `q_b`, `kv_a`,
  `kv_b`), routers, shared and routed experts and the image adapter; a
  depthwise convolution `conv_w` [K, C] gets 1/sqrt(K).
- an embedding table that is only looked up: normal, std 1; one that is
  tied to the LM head: 1/sqrt(d), its contraction there.
- norm scales (`scale`, `norm`, `q_a_norm`, `kv_a_norm`) and the SSM's
  skip `D`: ones; biases (`bias`, `conv_b`): zeros.
- the SSM's `A_log` = log U[1, 16] and `dt_bias` = softplus^-1(dt), dt =
  exp(U[log 1e-3, log 1e-1]) floored at 1e-4: Mamba-2's published
  initialisation (`A_init_range`, `dt_min`, `dt_max` and `dt_init_floor`
  of the `Mamba2` module in state-spaces/mamba).

The table covers every leaf of every family in the program's registry
(`repro.configs.all_archs.ALL_ARCHS`); `bench/tests/test_weights.py` holds
it to that. A leaf name with no rule is an error.

A rule's rank is that of one matrix or vector. Any leading axes (layers,
the groups of a VLM stack, experts) are drawn slice by slice over the
flattened leading index (a lax.map), each slice from `fold_in(key, j)`,
so the float32 draw of one slice at a time is all the scratch the call
holds.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

A_INIT_RANGE = (1.0, 16.0)
DT_MIN, DT_MAX, DT_INIT_FLOOR = 1e-3, 1e-1, 1e-4

# name -> (kind, rank of one slice, axes contracted over, relative to the
# slice's shape)
RULES = {
    "q": ("normal", 3, (0,)), "k": ("normal", 3, (0,)),
    "v": ("normal", 3, (0,)), "o": ("normal", 3, (0, 1)),
    "up": ("normal", 2, (0,)), "gate": ("normal", 2, (0,)),
    "down": ("normal", 2, (0,)), "unembed": ("normal", 2, (0,)),
    "tok": ("embedding", 2, (1,)),
    "scale": ("ones", 1, ()),
    # state-space mixer (mamba2, hymba)
    "in_proj": ("normal", 2, (0,)), "out_proj": ("normal", 2, (0,)),
    "conv_w": ("normal", 2, (0,)), "conv_b": ("zeros", 1, ()),
    "A_log": ("a_log", 1, ()), "dt_bias": ("dt_bias", 1, ()),
    "D": ("ones", 1, ()), "norm": ("ones", 1, ()),
    # latent attention (deepseek-v2)
    "q_a": ("normal", 2, (0,)), "q_b": ("normal", 3, (0,)),
    "kv_a": ("normal", 2, (0,)), "kv_b": ("normal", 3, (0,)),
    "q_a_norm": ("ones", 1, ()), "kv_a_norm": ("ones", 1, ()),
    # mixture of experts (deepseek-v2, dbrx)
    "router": ("normal", 2, (0,)),
    "shared_up": ("normal", 2, (0,)), "shared_gate": ("normal", 2, (0,)),
    "shared_down": ("normal", 2, (0,)),
    # layernorm (dbrx, whisper) and the VLM's image adapter
    "bias": ("zeros", 1, ()),
    "img_adapter": ("normal", 2, (0,)),
}


def seed_key(seed: int):
    """A PRNG key from any whole number: its two 32-bit halves are folded
    in, so seeds past 2**32 (and negative ones, taken mod 2**64) work."""
    s = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, s >> 32)
    return jax.random.fold_in(key, s & 0xFFFFFFFF)


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def _draw_one(key, kind: str, shape, contracted, dtype, tied: bool):
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    if kind == "a_log":
        a = jax.random.uniform(key, shape, jnp.float32, *A_INIT_RANGE)
        return jnp.log(a).astype(dtype)
    if kind == "dt_bias":
        u = jax.random.uniform(key, shape, jnp.float32, math.log(DT_MIN),
                               math.log(DT_MAX))
        dt = jnp.maximum(jnp.exp(u), DT_INIT_FLOOR)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if kind == "embedding" and not tied:
        std = 1.0
    else:
        std = 1.0 / math.sqrt(math.prod(shape[a] for a in contracted))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def leaf_plan(shapes) -> list[tuple[str, str, bool, tuple, tuple, object]]:
    """(path, kind, stacked, slice shape, contracted axes, dtype) for every
    leaf, in sorted path order; `stacked` says the leaf has leading axes
    beyond its rule's rank. Unknown leaf names are an error: the benchmark
    serves no model whose weights it has no rule for."""
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    plan = []
    for path, leaf in flat:
        name = _leaf_name(path)
        if name not in RULES:
            raise KeyError(f"no weight rule for leaf {jax.tree_util.keystr(path)}"
                           f" (name {name!r})")
        kind, rank, contracted = RULES[name]
        if leaf.ndim < rank:
            raise ValueError(f"leaf {jax.tree_util.keystr(path)} has rank "
                             f"{leaf.ndim}; the rule for {name!r} wants "
                             f"{rank} or more")
        plan.append((jax.tree_util.keystr(path), kind, leaf.ndim > rank,
                     tuple(leaf.shape[leaf.ndim - rank:]), contracted,
                     leaf.dtype))
    return plan


def draw(shapes, seed: int):
    """The whole parameter tree, drawn on the default device in one
    jitted call. `shapes` is `jax.eval_shape(model.init, key)`."""
    flat, treedef = jax.tree_util.tree_flatten(shapes)
    plan = leaf_plan(shapes)
    order = sorted(range(len(plan)), key=lambda i: plan[i][0])
    index = {i: rank for rank, i in enumerate(order)}
    tied = not any(p[0].endswith("['unembed']") for p in plan)

    def make(key):
        leaves = []
        for i, (path, kind, stacked, per_slice, contracted, dtype) in \
                enumerate(plan):
            k = jax.random.fold_in(key, index[i])
            if stacked:
                lead = flat[i].shape[:flat[i].ndim - len(per_slice)]
                leaves.append(jax.lax.map(
                    lambda j, k=k, kind=kind, s=per_slice, c=contracted,
                    d=dtype: _draw_one(jax.random.fold_in(k, j), kind, s, c,
                                       d, tied),
                    jnp.arange(math.prod(lead))).reshape(flat[i].shape))
            else:
                leaves.append(_draw_one(k, kind, per_slice, contracted, dtype,
                                        tied))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(make)(seed_key(seed))
