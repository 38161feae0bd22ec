"""Traffic generator: turns a mix's parameter file into requests.

One generator reads every mix in ``bench/traffic/<name>.json``. The work
in a window does not depend on the seed: lengths are quantiles of the
stated distribution at stratified points ``(i + 0.5) / n``, and Poisson
gaps are exponential quantiles at the same points, scaled so that ``n``
arrivals span exactly the window. The seed permutes the lengths' order,
draws the token ids, picks which requests decode greedily, and gives each
sampled request its sampling seed. The gaps' order, which places the
bursts and lulls of the Poisson process, is drawn from the mix's
``arrival_seed`` where it has one (one realised arrival pattern, the same
for every run seed) and from the run's seed otherwise.

Two kinds of mix:

* ``open``: arrivals on a schedule at ``rate_per_s``, independent of the
  server. Arrivals start ``lead_s`` before the window; those are
  :attr:`Spec.in_window` False and are not counted.
* ``closed``: a backlog of ``pool`` requests that the harness keeps
  queued ahead of the slots; it cycles through the pool in a new seeded
  order each pass.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Iterator, List, Optional

import numpy as np


@dataclasses.dataclass
class Spec:
    """One request as the generator made it."""
    index: int
    prompt: np.ndarray          # int32 token ids
    max_new_tokens: int
    greedy: bool
    sample_seed: int            # sampling seed (unused when greedy)
    due: float = 0.0            # seconds from the window's start (open mix)
    in_window: bool = True


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths: the distribution's quantiles at ``(i + 0.5) / n``,
    ascending, rounded and clipped to ``[min, max]``. Only ``lognormal``
    is known."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    nd = NormalDist()
    mu, sigma = math.log(dist["median"]), dist["sigma"]
    q = [math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n)) for i in range(n)]
    return np.clip(np.rint(q), dist["min"], dist["max"]).astype(np.int64)


# strides of low-discrepancy orders, one per drawn length, so that the
# length's quantiles fall evenly along the arrival order and the two are
# not tied to each other
STRIDES = {"prompt": 0.6180339887, "output": 0.4142135624}


def seeded_order(n: int, rng, block: int, stride: float) -> np.ndarray:
    """An order of ``range(n)`` drawn from ``rng``. With ``block`` 0, any
    permutation. Otherwise every run of ``block`` consecutive positions
    holds values spread over the whole range (position ``p`` starts at
    ``p * k mod n`` with ``k / n`` near ``stride``), and the seed shuffles
    within each run: the work in any stretch of the window is then about
    the same for every seed."""
    if not block:
        return rng.permutation(n)
    k = max(1, round(n * stride))
    while math.gcd(k, n) != 1:
        k += 1
    base = (np.arange(n) * k) % n
    return np.concatenate([rng.permutation(base[i:i + block])
                           for i in range(0, n, block)])


def exp_gaps(n: int, total: float) -> np.ndarray:
    """``n`` exponential gaps at stratified quantiles, scaled to sum to
    ``total`` (so every window holds the same number of arrivals)."""
    g = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    return g * (total / g.sum())


class Traffic:
    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix = mix
        self.kind = mix["kind"]
        if self.kind not in ("open", "closed"):
            raise ValueError(f"unknown traffic kind {self.kind!r}")
        self.rng = np.random.default_rng(seed)
        self.vocab = vocab
        self._next_index = 0

    def _order(self, n: int, what: str) -> np.ndarray:
        return seeded_order(n, self.rng, self.mix.get("order_block", 0),
                            STRIDES[what])

    def _gaps(self, n: int, total: float) -> np.ndarray:
        """``n`` Poisson gaps summing to ``total``, in a random order: the
        mix's fixed one where it names an ``arrival_seed``."""
        fixed = self.mix.get("arrival_seed")
        rng = self.rng if fixed is None else np.random.default_rng([fixed, n])
        return exp_gaps(n, total)[rng.permutation(n)]

    def _specs(self, n: int) -> List[Spec]:
        """``n`` requests over the stratified length sets, in seeded order."""
        prompts = quantiles(self.mix["prompt"], n)[self._order(n, "prompt")]
        outputs = quantiles(self.mix["output"], n)[self._order(n, "output")]
        every = self.mix.get("greedy_every", 1)
        flags = np.arange(n) % every == 0
        block = self.mix.get("order_block", 0)
        greedy = (np.concatenate([self.rng.permutation(flags[i:i + block])
                                  for i in range(0, n, block)])
                  if block else self.rng.permutation(flags))
        out = []
        for p, o, g in zip(prompts, outputs, greedy):
            out.append(Spec(
                index=self._next_index,
                prompt=self.rng.integers(1, self.vocab, int(p)).astype(np.int32),
                max_new_tokens=int(o), greedy=bool(g),
                sample_seed=int(self.rng.integers(2 ** 31 - 1))))
            self._next_index += 1
        return out

    # -- open loop --------------------------------------------------------------
    def schedule(self, seconds: float) -> List[Spec]:
        """Open mix: the requests due from ``-lead_s`` to ``seconds``, each
        with its due time relative to the window's start, sorted by it."""
        rate, lead = self.mix["rate_per_s"], self.mix["lead_s"]
        n_lead = max(1, round(rate * lead))
        n_win = max(1, round(rate * seconds))
        lead_specs = self._specs(n_lead)
        t = -lead
        lead_gaps = self._gaps(n_lead, lead)
        for s, g in zip(lead_specs, lead_gaps):
            s.due, s.in_window = t, False
            t += g
        win_specs = self._specs(n_win)
        t = 0.0
        win_gaps = self._gaps(n_win, seconds)
        for s, g in zip(win_specs, win_gaps):
            s.due = t
            t += g
        return lead_specs + win_specs

    # -- closed loop ------------------------------------------------------------
    def backlog(self) -> Iterator[Spec]:
        """Closed mix: endless requests, ``pool`` stratified ones per pass."""
        while True:
            yield from self._specs(self.mix["pool"])

    def lengths(self, seconds: Optional[float] = None) -> set:
        """Every prompt length this mix can draw (for warming up paths
        whose programs depend on it); the same for every seed."""
        sizes = []
        if self.kind == "open":
            rate = self.mix["rate_per_s"]
            sizes = [max(1, round(rate * self.mix["lead_s"])),
                     max(1, round(rate * seconds))]
        else:
            sizes = [self.mix["pool"]]
        return {int(x) for n in sizes for x in quantiles(self.mix["prompt"], n)}
