"""The one traffic generator: reads a mix's parameters (bench/traffic/
<mix>.json) and makes its requests from ``--seed``.

Every seed gets the same work in another order.  Sizes and inter-arrival
gaps are stratified quantiles of the mix's distributions, drawn once for
a pool of ``pool`` requests; the seed permutes them (cycling for a closed
loop) and draws the contents (token ids, latents, text).  So two seeds
differ in what is computed, not in how much.

Distributions (``{"dist": ...}``):
    fixed      {"value": v}
    uniform    {"lo": a, "hi": b}                  integers in [a, b]
    lognormal  {"median": m, "sigma": s, "lo": a, "hi": b}   clipped

Loops:
    closed     ``clients`` requests in flight; a finished one is replaced
    open       Poisson arrivals at ``rate`` per second
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np


def _quantiles(spec: dict, n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    kind = spec["dist"]
    if kind == "fixed":
        return np.full(n, spec["value"], np.int64)
    if kind == "uniform":
        return np.round(spec["lo"] + u * (spec["hi"] - spec["lo"])).astype(
            np.int64)
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
        return np.clip(np.round(x), spec["lo"], spec["hi"]).astype(np.int64)
    raise ValueError(f"unknown distribution {kind!r}")


class Traffic:
    """Seeded request sizes and arrival times of one mix."""

    def __init__(self, mix: dict, seed: int):
        self.mix = mix
        self.seed = seed
        self.rng = np.random.default_rng(seed)
        n = int(mix.get("pool", 256))
        order = self.rng.permutation(n)
        self.sizes = {k: _quantiles(v, n)[order if i == 0 else
                                          self.rng.permutation(n)]
                      for i, (k, v) in enumerate(sorted(mix["sizes"].items()))}
        self.n = n
        self.loop = mix["loop"]
        if self.loop == "open":
            rate = float(mix["rate"])
            gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n) / rate
            self.due = np.cumsum(self.rng.permutation(gaps))
        self._next = 0

    def size(self, i: int) -> dict:
        return {k: int(v[i % self.n]) for k, v in self.sizes.items()}

    def next_index(self) -> int:
        i = self._next
        self._next += 1
        return i

    def due_times(self, horizon: float) -> list:
        """Open loop: due times (seconds after the window opens) up to
        ``horizon``, repeating the pool's gaps if it runs short."""
        out, base, i = [], 0.0, 0
        while True:
            t = base + float(self.due[i % self.n])
            if t > horizon:
                return out
            out.append(t)
            i += 1
            if i % self.n == 0:
                base = t
        return out


def token_ids(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return rng.integers(1, vocab, n).astype(np.int32)

