"""The weight rule covers every family in the program's registry, and the
draws and specs that yi6b_chat reads stay bit for bit as recorded."""

import dataclasses
import hashlib
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cells
import loadgen
import run
import weights
from repro.configs.all_archs import ALL_ARCHS
from repro.configs.base import get_arch, reduced
from repro.models.model import Model


def _shapes(cfg):
    return jax.eval_shape(Model(cfg, use_pallas=True).init,
                          jax.random.PRNGKey(0))


def _by_name(tree) -> dict:
    out: dict = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out.setdefault(weights._leaf_name(path), []).append(
            np.asarray(leaf, np.float32))
    return out


def _rounded(x, dtype) -> float:
    return float(np.asarray(jnp.asarray(x, dtype), np.float32))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_every_registry_family_has_a_rule(arch):
    shapes = _shapes(reduced(get_arch(arch)))
    plan = weights.leaf_plan(shapes)
    assert len(plan) == len(jax.tree.leaves(shapes))
    params = weights.draw(shapes, 2 ** 33 + 17)
    assert jax.tree.structure(params) == jax.tree.structure(shapes)
    for p, s in zip(jax.tree.leaves(params), jax.tree.leaves(shapes)):
        assert p.shape == s.shape and p.dtype == s.dtype
        assert np.isfinite(np.asarray(p, np.float32)).all()
    leaves = _by_name(params)
    dtypes = {weights._leaf_name(k): v.dtype for k, v in
              jax.tree_util.tree_flatten_with_path(shapes)[0]}
    # Mamba-2's published ranges, rounded as the leaf's dtype rounds
    softplus_inv = lambda dt: dt + math.log(-math.expm1(-dt))
    ranges = {"A_log": (0.0, math.log(16.0)),
              "dt_bias": (softplus_inv(1e-3), softplus_inv(1e-1)),
              "D": (1.0, 1.0)}
    for name, (lo, hi) in ranges.items():
        for x in leaves.get(name, []):
            d = dtypes[name]
            assert _rounded(lo, d) <= x.min() and x.max() <= _rounded(hi, d)
            if name != "D":
                assert x.max() - x.min() > 0.1 * (hi - lo)
    for name in ("conv_b", "bias"):
        assert all((x == 0).all() for x in leaves.get(name, []))
    for name in ("scale", "norm", "q_a_norm", "kv_a_norm"):
        assert all((x == 1).all() for x in leaves.get(name, []))


def test_leading_axes_are_drawn_slice_by_slice():
    """Experts of every layer, and the [groups, in-group] stacks of a VLM,
    are drawn apart, each at its own slice's scale."""
    shapes = {"moe": {"up": jax.ShapeDtypeStruct((3, 4, 64, 32),
                                                 jnp.bfloat16)},
              "plain": {"q": jax.ShapeDtypeStruct((2, 2, 64, 4, 16),
                                                  jnp.bfloat16)}}
    p = weights.draw(shapes, 9)
    up = np.asarray(p["moe"]["up"], np.float32).reshape(12, 64, 32)
    q = np.asarray(p["plain"]["q"], np.float32).reshape(4, 64, 4, 16)
    for x in (up, q):
        assert len({x[j].tobytes() for j in range(len(x))}) == len(x)
        assert x.std() == pytest.approx(1 / 8, rel=0.1)
    plan = [p[1:5] for p in weights.leaf_plan(shapes)]
    assert plan == [("normal", True, (64, 32), (0,)),
                    ("normal", True, (64, 4, 16), (0,))]


def test_a_leaf_below_its_rules_rank_fails():
    with pytest.raises(ValueError):
        weights.leaf_plan({"q": jax.ShapeDtypeStruct((64, 16), jnp.bfloat16)})


def _as_file(cfg, groups_in: str) -> dict:
    """A configuration file, through JSON, that gives every field of `cfg`:
    a published key for each top-level field, and each field of a nested
    group dotted, in `program_keys` or in `program_fixed`."""
    conf = {"name": cfg.name, "program_keys": {}, "program_fixed": {}}
    for f in dataclasses.fields(cfg):
        v = getattr(cfg, f.name)
        if f.name == "name":
            continue
        if not dataclasses.is_dataclass(v):
            conf[f"pub_{f.name}"] = v
            conf["program_keys"][f.name] = f"pub_{f.name}"
            continue
        for g in dataclasses.fields(v):
            dotted = f"{f.name}.{g.name}"
            if groups_in == "program_fixed":
                conf["program_fixed"][dotted] = getattr(v, g.name)
            else:
                conf[f"pub_{f.name}"] = dict(conf.get(f"pub_{f.name}", {}),
                                             **{g.name: getattr(v, g.name)})
                conf["program_keys"][dotted] = f"pub_{dotted}"
    return json.loads(json.dumps(conf))


@pytest.mark.parametrize("groups_in", ["program_keys", "program_fixed"])
@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_arch_config_builds_nested_groups(arch, groups_in):
    cfg = reduced(get_arch(arch))
    assert run.arch_config(_as_file(cfg, groups_in)) == cfg


def test_arch_config_refuses_an_unknown_group():
    conf = cells.load_json("configs", "tiny-dense")
    conf["program_fixed"]["rope.theta"] = 1.0
    with pytest.raises(ValueError):
        run.arch_config(conf)


# Recorded from the harness before it took every registry family: the
# weights, plan, ArchConfig and schedule that yi6b_chat reads.
PINNED = {
    "draw tiny-dense 2**33+5":
        "89f5f70ae8ce25c2db21a36774ad58aaf24ad4a3c5c8d8796f2f1a4c47e6a4fb",
    "draw tiny-dense 5":
        "75f84a2d4ca2d1fd1c5afe9afe5df829be56bfd1fffafcfc215181898e06ac59",
    "plan yi-6b":
        "1a355d6568906d7a988bd3e93e9d8144264aabfc3aefc0c39ecd2d5f28a16a72",
    "arch_config yi-6b":
        "67170f37a6a931a6e72d1ccfcad147ed99e04623dd6758b3d3b453f14f2261ce",
    "open_loop yi6b_chat 1 51":
        "491f6f679ad382f3b7ad54c8567c34c5f1d6d59d1bbc677d44876432adad7b03",
    "open_loop yi6b_chat 12 51":
        "9adebaaf013c74e19ec113603dc78db2404ea93e880c7554937a5677655630d9",
    "open_loop yi6b_chat 3000000019 51":
        "9adebaaf013c74e19ec113603dc78db2404ea93e880c7554937a5677655630d9",
}


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()


def _tree_digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)}|{a.dtype}|{a.shape}|"
                 .encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _config_shapes(name):
    conf = cells.load_json("configs", name)
    return _shapes(run.arch_config(conf))


@pytest.mark.parametrize("what", sorted(PINNED))
def test_yi6b_chat_inputs_are_pinned(what):
    kind, name, *args = what.split()
    if kind == "draw":
        seed = 2 ** 33 + 5 if args[0] == "2**33+5" else int(args[0])
        got = _tree_digest(weights.draw(_config_shapes(name), seed))
    elif kind == "plan":
        got = _sha(repr(weights.leaf_plan(_config_shapes(name))))
    elif kind == "arch_config":
        got = _sha(repr(run.arch_config(cells.load_json("configs", name))))
    else:
        mix = cells.load_json("traffic", name)
        got = _sha(repr(loadgen.open_loop(mix, int(args[0]),
                                          float(args[1]))))
    assert got == PINNED[what]
