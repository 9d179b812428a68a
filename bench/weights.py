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
  below bf16 rounding).
- an embedding table that is only looked up: normal, std 1; one that is
  tied to the LM head: 1/sqrt(d), its contraction there.
- norm scales: ones.

A model family with other leaves (a state-space mixer's A_log, dt_bias,
D) adds their rules here with the cell that serves it.

A leaf with a leading layer axis is drawn layer by layer (a lax.map), each
layer from its own key, so the float32 draw of one layer at a time is all
the scratch the call holds.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# name -> (kind, per-layer rank, axes contracted over, relative to the
# per-layer shape)
RULES = {
    "q": ("normal", 3, (0,)), "k": ("normal", 3, (0,)),
    "v": ("normal", 3, (0,)), "o": ("normal", 3, (0, 1)),
    "up": ("normal", 2, (0,)), "gate": ("normal", 2, (0,)),
    "down": ("normal", 2, (0,)), "unembed": ("normal", 2, (0,)),
    "tok": ("embedding", 2, (1,)),
    "scale": ("ones", 1, ()),
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
    if kind == "embedding" and not tied:
        std = 1.0
    else:
        std = 1.0 / math.sqrt(math.prod(shape[a] for a in contracted))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def leaf_plan(shapes) -> list[tuple[str, str, bool, tuple, tuple, object]]:
    """(path, kind, stacked, per-layer shape, contracted axes, dtype) for
    every leaf, in sorted path order. Unknown leaf names are an error: the
    benchmark serves no model whose weights it has no rule for."""
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    plan = []
    for path, leaf in flat:
        name = _leaf_name(path)
        if name not in RULES:
            raise KeyError(f"no weight rule for leaf {jax.tree_util.keystr(path)}"
                           f" (name {name!r})")
        kind, rank, contracted = RULES[name]
        if leaf.ndim == rank + 1:
            stacked, per_layer = True, tuple(leaf.shape[1:])
        elif leaf.ndim == rank:
            stacked, per_layer = False, tuple(leaf.shape)
        else:
            raise ValueError(f"leaf {jax.tree_util.keystr(path)} has rank "
                             f"{leaf.ndim}; the rule for {name!r} wants "
                             f"{rank} (or {rank + 1} stacked)")
        plan.append((jax.tree_util.keystr(path), kind, stacked, per_layer,
                     contracted, leaf.dtype))
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
        for i, (path, kind, stacked, per_layer, contracted, dtype) in \
                enumerate(plan):
            k = jax.random.fold_in(key, index[i])
            if stacked:
                n = flat[i].shape[0]
                leaves.append(jax.lax.map(
                    lambda j, k=k, kind=kind, s=per_layer, c=contracted,
                    d=dtype: _draw_one(jax.random.fold_in(k, j), kind, s, c,
                                       d, tied),
                    jnp.arange(n)))
            else:
                leaves.append(_draw_one(k, kind, per_layer, contracted, dtype,
                                        tied))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(make)(seed_key(seed))
