"""Traffic and weights come from the seed: the same seed gives the same
inputs, and every seed the same work in another order."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cells
import loadgen
import run
import weights


@pytest.mark.parametrize("name", ["yi6b_chat", "rehearsal_chat"])
def test_open_loop_same_work_every_seed(name):
    mix = cells.load_json("traffic", name)
    a = loadgen.open_loop(mix, 3_000_000_011, 30.0)
    b = loadgen.open_loop(mix, 3_000_000_011, 30.0)
    c = loadgen.open_loop(mix, 12, 30.0)
    assert a == b
    assert a != c
    # the same requests at the same times, the mean rate exact
    assert len(a) == len(c) == round(mix["arrivals"]["rate_per_s"] * 30)
    assert [x.due for x in a] == [x.due for x in c]
    assert max(x.due for x in a) < 30.0
    sizes = lambda s: collections.Counter((x.prompt_len, x.out_len)
                                          for x in s)
    assert sizes(a) == sizes(c)
    # and each position keeps a request of its class
    for x, y in zip(a, c):
        assert loadgen._bucket(x.prompt_len) == loadgen._bucket(y.prompt_len)
        assert x.out_len // loadgen.CLASS_TOKENS == \
            y.out_len // loadgen.CLASS_TOKENS
    lo, hi = loadgen.length_bounds(mix)
    assert all(lo <= x.prompt_len <= hi for x in a)
    ta = loadgen.TokenSource(7, 1000)
    tb = loadgen.TokenSource(7, 1000)
    assert all((ta(s) == tb(s)).all() for s in a[:5])


@pytest.mark.parametrize("config", ["tiny-dense"])
def test_weight_rule(config):
    from repro.models.model import Model
    conf = cells.load_json("configs", config)
    model = Model(run.arch_config(conf), use_pallas=True)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p1 = weights.draw(shapes, 2 ** 33 + 5)
    p2 = weights.draw(shapes, 2 ** 33 + 5)
    p3 = weights.draw(shapes, 5)
    assert all(bool((a == b).all()) for a, b in
               zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    assert jax.tree.structure(p1) == jax.tree.structure(shapes)
    flat = dict((jax.tree_util.keystr(k), v) for k, v in
                jax.tree_util.tree_flatten_with_path(p1)[0])
    other = dict((jax.tree_util.keystr(k), v) for k, v in
                 jax.tree_util.tree_flatten_with_path(p3)[0])
    for path, v in flat.items():
        assert v.dtype == jnp.bfloat16
        f = np.asarray(v, np.float32)
        if path.endswith("['scale']"):
            assert (f == 1).all()
        else:
            assert not (f == np.asarray(other[path], np.float32)).all()
    d = conf["hidden_size"]
    q = np.asarray(flat["['layers']['attn']['q']"], np.float32)
    assert q.std() == pytest.approx(1 / np.sqrt(d), rel=0.1)
    o = np.asarray(flat["['layers']['attn']['o']"], np.float32)
    assert o.std() == pytest.approx(1 / np.sqrt(o.shape[1] * o.shape[2]),
                                    rel=0.1)
    assert np.asarray(flat["['embed']['tok']"], np.float32).std() == \
        pytest.approx(1.0, rel=0.1)
    # layers of a stacked leaf are drawn apart
    some = next(v for k, v in flat.items() if k.endswith("['up']"))
    assert not (np.asarray(some[0]) == np.asarray(some[1])).all()


def test_unknown_leaf_has_no_rule():
    shapes = {"embed": {"tok": jax.ShapeDtypeStruct((8, 4), jnp.bfloat16)},
              "mystery": jax.ShapeDtypeStruct((4, 4), jnp.bfloat16)}
    with pytest.raises(KeyError):
        weights.draw(shapes, 1)


def test_gamma_arrivals_keep_cv_and_rate():
    a = {"process": "gamma", "cv": 3.0, "rate_per_s": 2.5}
    gaps = loadgen._gaps(np.random.default_rng(5), a, 4096)
    assert 2.5 <= gaps.std() / gaps.mean() <= 3.5
    mix = dict(cells.load_json("traffic", "yi6b_chat"), arrivals=a)
    due = np.array([s.due for s in loadgen.open_loop(mix, 2 ** 40 + 1, 40.0)])
    # the mean rate over the window is exactly the mix's rate
    assert len(due) / 40.0 == 2.5
    assert due[0] == 0.0 and (np.diff(due) >= 0).all() and due[-1] < 40.0
    # bursts: a gamma schedule of CV 3 packs more requests into its
    # busiest second than a Poisson one at the same rate
    poisson = dict(mix, arrivals=dict(a, process="poisson"))
    busiest = lambda m: np.bincount(np.floor(np.array(
        [s.due for s in loadgen.open_loop(m, 7, 40.0)])).astype(int)).max()
    assert busiest(mix) > busiest(poisson)


def test_unknown_arrival_process_fails():
    with pytest.raises(ValueError):
        loadgen._gaps(np.random.default_rng(0), {"process": "weibull"}, 8)
