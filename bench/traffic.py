"""The one traffic generator: reads a mix's parameters from
``traffic/<mix>.json`` and turns them, with a seed, into requests.

A mix is a closed loop: every slot holds a request, ``backlog`` more
wait in the queue, and each completion submits one more. The loop opens
in its steady state: the requests that fill the slots first take, as
their answer length, the remaining length of a request caught partway
(the residual-life distribution of ``max_new``), so completions and
refills run at their steady rate from the first step instead of the
whole batch finishing together after one fill.

Every seed gets the same work. Lengths are fixed quantiles of the mix's
distributions: each run of ``block`` consecutive requests holds the same
``block`` quantiles of prompt length and of answer length, and the first
``n_slots`` requests the same ``n_slots`` quantiles of the residual
length. The seed only orders and pairs them and draws the prompt token
ids.

Mix keys:
  loop        "closed" (the only kind so far).
  backlog     requests waiting beyond the slots.
  block       requests in one stratum of the length quantiles.
  prompt_len, max_new
              {"dist": "lognormal", "median", "sigma", "min", "max"} or
              {"dist": "uniform", "min", "max"}, in tokens.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Req:
    prompt: list
    max_new: int


def load(path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def _quantiles(count: int):
    return (np.arange(count) + 0.5) / count


def lengths(spec: dict, count: int) -> np.ndarray:
    """``count`` fixed quantiles of a length distribution, as ints."""
    u = _quantiles(count)
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = spec["min"] + u * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(int)


def residual_lengths(spec: dict, count: int, grid: int = 1024) -> np.ndarray:
    """``count`` fixed quantiles of the tokens a request still has to
    produce when caught at a random decode step: a request of length L
    is caught in proportion to L, with 1..L tokens left equally likely."""
    full = lengths(spec, grid)
    r = np.arange(1, int(full.max()) + 1)
    cdf = np.minimum(r[:, None], full[None, :]).sum(axis=1) / full.sum()
    return r[np.searchsorted(cdf, _quantiles(count))]


def prompt_buckets(mix: dict, s_max: int, next_pow2) -> list:
    """The padded prompt widths a fill of this mix can take under the
    engine's power-of-two bucketing (``next_pow2``)."""
    lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    out = set()
    for n in range(lo, hi + 1):
        b = next_pow2(n)
        out.add(b if b < s_max else n)
    return sorted(out)


class Traffic:
    """The closed loop's endless request sequence under one seed, for an
    engine of ``n_slots`` slots. ``vocab_ids`` bounds the prompt token
    ids (drawn from [1, vocab_ids))."""

    def __init__(self, mix: dict, seed: int, n_slots: int, vocab_ids: int):
        if mix["loop"] != "closed":
            raise ValueError(f"unknown loop {mix['loop']!r}")
        self.mix, self.seed, self.vocab_ids = mix, seed, vocab_ids
        self.backlog = int(mix["backlog"])
        self.rng = np.random.default_rng(seed)
        self._n = 0
        self._prompts = []
        self._answers = []
        self._first = self.rng.permutation(
            residual_lengths(mix["max_new"], n_slots)).tolist()

    def _prompt(self, n: int) -> list:
        rng = np.random.default_rng([self.seed, self._n])
        self._n += 1
        return rng.integers(1, self.vocab_ids, size=int(n)).tolist()

    def _draw(self, pool: list, spec: dict) -> int:
        if not pool:
            pool.extend(self.rng.permutation(lengths(spec, int(self.mix["block"]))))
        return int(pool.pop())

    def next(self) -> Req:
        """The next request: the first ``n_slots`` with residual answer
        lengths, every later one with a full answer length."""
        p = self._draw(self._prompts, self.mix["prompt_len"])
        if self._first:
            m = self._first.pop()
        else:
            m = self._draw(self._answers, self.mix["max_new"])
        return Req(self._prompt(p), int(m))
