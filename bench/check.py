"""The comparison that decides `correct`.

Served tokens are greedy, so each one should be the float32 reference's
best token at its position, up to rounding. After the window a sample of
finished requests, drawn from the seed with the longest one always in it,
is run through the family's plain reference (refs/<family>.py) once:
prompt and served tokens as one sequence, layer by layer. For every
served token the gap is

    (max_v ref_logit[v] - ref_logit[served]) / std_v(ref_logit)

at the position that produced it: 0 when the served token is the
reference's best, small when rounding flipped a near tie, large when the
program computed something else. The number compared is the widest gap.

The control puts the reference itself in the program's place at the
nearest precision below bf16 (float8 e4m3 weights and activations on
every projection and the LM head, refmath.mm) and reads, at each position, the
gap of the token that the fp8 forward puts first.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ROUND = 128          # sequence and position counts are padded to this


def sample(done: list, seed: int, tokens: int, max_requests: int) -> list:
    """Finished requests to compare: the longest (prompt plus output),
    then others in an order drawn from `seed`, until `tokens` served tokens
    or `max_requests` requests are in."""
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.out), r.rid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed % (1 << 64), 3])
    picks = [longest]
    for i in rng.permutation(len(rest)):
        if sum(len(r.out) for r in picks) >= tokens or \
                len(picks) >= max_requests:
            break
        picks.append(rest[i])
    return picks


def _rup(n: int) -> int:
    return -(-n // ROUND) * ROUND


def _batch(picks, max_requests: int):
    """Token matrix [B, T] (prompt, then served tokens but the last),
    the positions that produced each served token [B, J], the served
    tokens [B, J] and their mask."""
    B = max_requests
    T = _rup(max(len(r.prompt) + len(r.out) - 1 for r in picks))
    J = _rup(max(len(r.out) for r in picks))
    toks = np.zeros((B, T), np.int32)
    pos = np.zeros((B, J), np.int32)
    served = np.zeros((B, J), np.int32)
    mask = np.zeros((B, J), bool)
    for b, r in enumerate(picks):
        seq = np.concatenate([np.asarray(r.prompt, np.int32),
                              np.asarray(r.out[:-1], np.int32)])
        toks[b, :len(seq)] = seq
        n = len(r.out)
        pos[b, :n] = len(r.prompt) - 1 + np.arange(n)
        served[b, :n] = r.out
        mask[b, :n] = True
    return toks, pos, served, mask


_FNS: dict = {}


def _fns(ref, conf: dict):
    """The reference's jitted embed, layer and head-at-positions, made
    once per (family, configuration) in a process."""
    key = (ref.__name__, conf["name"])
    if key in _FNS:
        return _FNS[key]
    with jax.default_matmul_precision("highest"):
        embed = jax.jit(lambda p, t: ref.embed(conf, p, t))
        layer = jax.jit(lambda p, i, h, quant: ref.layer(conf, p, i, h, quant),
                        static_argnames="quant")

        @functools.partial(jax.jit, static_argnames="quant")
        def logits_at(p, h, pos, quant):
            hw = jnp.take_along_axis(h, pos[..., None], axis=1)
            return ref.head(conf, p, hw, quant)
    _FNS[key] = embed, layer, logits_at
    return _FNS[key]


def _hidden(ref, conf, params, toks, quant):
    embed, layer, _ = _fns(ref, conf)
    with jax.default_matmul_precision("highest"):
        h = embed(params, toks)
        for i in range(ref.n_layers(conf)):
            h = layer(params, i, h, quant=quant)
    return h


@jax.jit
def _gap(logits, tok):
    top = logits.max(axis=-1)
    got = jnp.take_along_axis(logits, tok[..., None], axis=-1)[..., 0]
    return (top - got) / logits.std(axis=-1)


def gaps(ref, conf: dict, params, picks, max_requests: int,
         control: bool = False) -> dict:
    """Gap of every served token of `picks`, a list per request, under
    "program"; with `control`, also under "control" the gap of the token
    that the fp8 reference puts first at the same positions."""
    toks, pos, served, mask = _batch(picks, max_requests)
    _, _, logits_at = _fns(ref, conf)
    out = {}
    with jax.default_matmul_precision("highest"):
        h = _hidden(ref, conf, params, jnp.asarray(toks), None)
        ref_logits = logits_at(params, h, jnp.asarray(pos), quant=None)
        del h
        toks_at = {"program": jnp.asarray(served)}
        if control:
            hq = _hidden(ref, conf, params, jnp.asarray(toks), "fp8")
            q_logits = logits_at(params, hq, jnp.asarray(pos), quant="fp8")
            del hq
            toks_at["control"] = jnp.argmax(q_logits, -1).astype(jnp.int32)
            del q_logits
        for name, tok in toks_at.items():
            g = np.asarray(_gap(ref_logits, tok))
            out[name] = [g[b][mask[b]] for b in range(len(picks))]
    return out
