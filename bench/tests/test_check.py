"""The comparison that decides `correct`, driven through the harness on
the CPU at rehearsal size (the chip look skipped): it passes the program,
fails the float8 control, and fails the program with its timed path
broken underneath.

Faults that a one-chip serving cell can have: a token altered where it is
produced (the decode chunk's returned tokens), and a step that returns
its state unchanged (the decode step hands back the cache it was given).
Serving takes no mean over a batch and this cell has no exchange between
chips, so those two faults have no place here.
"""

import contextlib

import pytest

import run

SECONDS = 3.0


def _cell(config):
    return run.cell_for(None, f"{config}:rehearsal_chat")


@contextlib.contextmanager
def token_altered():
    from repro.serve.engine import ServeEngine
    orig = ServeEngine._decode_chunk_impl

    def altered(self, *a, **kw):
        cache, seq, emits, stats = orig(self, *a, **kw)
        return cache, (seq + 1) % self.model.cfg.vocab, emits, stats
    ServeEngine._decode_chunk_impl = altered
    try:
        yield
    finally:
        ServeEngine._decode_chunk_impl = orig


@contextlib.contextmanager
def state_unchanged():
    from repro.models.model import Model
    orig = Model.decode_step

    def stale(self, params, tokens, cache, position):
        logits, _ = orig(self, params, tokens, cache, position)
        return logits, cache
    Model.decode_step = stale
    try:
        yield
    finally:
        Model.decode_step = orig


@pytest.mark.parametrize("config", ["tiny-dense"])
@pytest.mark.parametrize("fault", [None, token_altered, state_unchanged])
def test_faults_fail_the_check(config, fault):
    with (fault() if fault else contextlib.nullcontext()):
        res = run.run_cell(_cell(config), 901, SECONDS, trace=False)
    gap = res["check"]["gap_max"]
    assert res["correct"] is (fault is None), gap
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("config", ["tiny-dense"])
def test_control_fails_the_limit(config):
    import cells
    cell = _cell(config)
    conf = cells.load_json("configs", cell["config"])
    mix = cells.load_json("traffic", cell["traffic"])
    ref = cells.load_module("refs", conf["ref"])
    limit = conf["check"]["gap_limit"]
    st = run.Setup(conf, mix, 51, trace=False)
    for seed in (51, 52, 53):
        if seed != 51:
            st.reseed(seed)
        win = run.serve(st.loop(seed, False), mix, seed, SECONDS)
        widest, n_req, _ = run.compare(conf, ref, st.params, win, seed,
                                       control=True)
        assert n_req > 0
        assert widest["program"] <= limit < widest["control"], widest
