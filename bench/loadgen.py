"""The one traffic generator: a mix is a data file of parameters
(bench/traffic/<name>.json), read here.

Every seed gets the same work in another order. The sizes (prompt and
output lengths) and the arrival times are drawn once, in a base order,
from the mix's own `pool_seed`. The run's `--seed` draws the prompt tokens
and permutes the sizes, but only among requests of one class: the same
prompt bucket (the power of two the engine pads to) and the same output
length to within CLASS_TOKENS. A free permutation made a 51-second window
of yi6b_chat read a TTFT p90 that spread by 15-100% from seed to seed in
a simulation of the engine's schedule, and tok_s by 10%; permuting within
classes keeps both within a few percent, because every position of the
schedule still holds the same work.

Open loop (`"loop": "open"`): the first round(rate * seconds) requests of
the base order, due at the partial sums of their gaps, rescaled so that
the gaps add up to the window: the mean rate is exactly the mix's rate.
Arrival processes, each drawing gaps of mean 1 before the rescaling:

- `poisson`: exponential gaps (coefficient of variation 1).
- `gamma` with `"cv": c`: gamma gaps of shape 1/c**2 and scale c**2, so
  their coefficient of variation is c. c > 1 gives bursts: BurstGPT
  (arXiv:2401.17644) fits the gaps of served LLM traffic with a gamma
  distribution of CV well above 1, where a Poisson process has 1.

Length distributions: `lognormal` (median, sigma) and `uniform` (min..max),
each clipped to [min, max] and rounded to whole tokens.
"""

from __future__ import annotations

import dataclasses

import numpy as np

POOL_SIZE = 4096
CLASS_TOKENS = 8


@dataclasses.dataclass(frozen=True)
class Spec:
    idx: int
    due: float             # seconds after window start
    prompt_len: int
    out_len: int


def _lengths(rng, d: dict, n: int) -> np.ndarray:
    if d["dist"] == "lognormal":
        x = rng.lognormal(np.log(d["median"]), d["sigma"], n)
    elif d["dist"] == "uniform":
        x = rng.uniform(d["min"], d["max"] + 1, n)
    else:
        raise ValueError(f"unknown length distribution {d['dist']!r}")
    return np.clip(np.floor(x), d["min"], d["max"]).astype(np.int64)


def _gaps(rng, a: dict, n: int) -> np.ndarray:
    if a["process"] == "poisson":
        return rng.exponential(1.0, n)
    if a["process"] == "gamma":
        shape = 1.0 / a["cv"] ** 2
        return rng.gamma(shape, 1.0 / shape, n)
    raise ValueError(f"unknown arrival process {a['process']!r}")


def pool(mix: dict) -> tuple[np.ndarray, np.ndarray]:
    """The fixed multiset of (prompt, output) lengths of the mix."""
    rng = np.random.default_rng(mix["pool_seed"])
    return _lengths(rng, mix["prompt"], POOL_SIZE), \
        _lengths(rng, mix["output"], POOL_SIZE)


def length_bounds(mix: dict) -> tuple[int, int]:
    return int(mix["prompt"]["min"]), int(mix["prompt"]["max"])


def _bucket(n: int) -> int:
    return 1 << (max(8, n) - 1).bit_length()


def _within_classes(rng, prompts, outs) -> np.ndarray:
    """A permutation that moves each request only to the position of
    another of its class."""
    order = np.arange(len(prompts))
    classes: dict = {}
    for i, (p, o) in enumerate(zip(prompts, outs)):
        classes.setdefault((_bucket(int(p)), int(o) // CLASS_TOKENS),
                           []).append(i)
    for pos in classes.values():
        order[pos] = rng.permutation(pos)
    return order


def open_loop(mix: dict, seed: int, seconds: float,
              rate: float | None = None) -> list[Spec]:
    """Requests due in a window of `seconds` at the mix's rate (or `rate`,
    for a sweep), sizes permuted within classes by `seed`."""
    rate = mix["arrivals"]["rate_per_s"] if rate is None else rate
    n = max(1, int(round(rate * seconds)))
    if n > POOL_SIZE:
        raise ValueError(f"{n} requests exceed the pool of {POOL_SIZE}")
    prompts, outs = (a[:n] for a in pool(mix))
    gaps = _gaps(np.random.default_rng(mix["pool_seed"] + 1),
                 mix["arrivals"], n)
    gaps *= seconds / gaps.sum()
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    run = np.random.default_rng([seed % (1 << 64), 1])
    order = _within_classes(run, prompts, outs)
    return [Spec(i, float(due[i]), int(prompts[j]), int(outs[j]))
            for i, j in enumerate(order)]


class TokenSource:
    """Prompt tokens drawn on demand, in the order requests are made."""

    def __init__(self, seed: int, vocab: int):
        self._rng = np.random.default_rng([seed % (1 << 64), 2])
        self._vocab = vocab

    def __call__(self, spec: Spec) -> np.ndarray:
        return self._rng.integers(0, self._vocab, spec.prompt_len,
                                  dtype=np.int32)
