"""The one traffic generator.  A mix is a JSON file of parameters under
``bench/traffic/``; this module turns it and a seed into requests.

Lengths and inter-arrival gaps are drawn by strata: each block of
``strata`` consecutive requests takes the ``strata`` quantiles
``(k + 0.5) / strata`` of each distribution exactly once, in an order
shuffled by the mix's own ``schedule_seed``.  So every run of a mix
serves the same lengths at the same times, and only the token ids change
with the run's seed: the program's cost per round depends on which
prompts join together, and an order drawn from the run's seed changed
the work from seed to seed, not only the inputs.  Token ids are uniform
over the vocabulary, drawn from the run's seed and the request id.

Arrivals are either ``closed`` (the client keeps ``queued_per_slot`` x
batch requests waiting, so a freed slot always finds work: offline batch
generation) or ``poisson`` (open loop: request ``i`` is due at the sum of
the first ``i + 1`` exponential gaps at ``rate_per_s``, whether or not
earlier requests have finished).

A closed backlog starts from steady state: the first
``batch`` requests, which fill the slots at once, are given what a slot
holds at a random moment of a long run, the remaining part of an output
whose length is drawn in proportion to itself (a slot spends longer on a
long request), so the ramp need not wait a whole request lifetime for
the slots' ages to spread.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

_NORMAL = NormalDist()


@dataclasses.dataclass(frozen=True)
class Request:
    rid: int
    prompt: list[int]
    out_len: int
    due: float | None       # seconds after the schedule starts (open loop)


def quantile(dist: dict, u: float) -> int:
    """The ``u`` quantile of a length distribution, as a whole number."""
    kind = dist["dist"]
    if kind == "fixed":
        return int(dist["value"])
    if kind == "lognormal":
        x = math.exp(math.log(dist["median"])
                     + dist["sigma"] * _NORMAL.inv_cdf(u))
        return int(min(max(round(x), dist["min"]), dist["max"]))
    raise ValueError(f"unknown length distribution {kind!r}")


def length_range(dist: dict) -> tuple[int, int]:
    if dist["dist"] == "fixed":
        return int(dist["value"]), int(dist["value"])
    return int(dist["min"]), int(dist["max"])


class RequestStream:
    """Request ``i`` of a mix under one seed; an endless, repeatable
    stream (``get(i)`` is the same every time it is asked)."""

    def __init__(self, mix: dict, seed: int, vocab: int, batch: int = 0):
        self.mix, self.seed, self.vocab = mix, seed, vocab
        self.order_seed = int(mix["schedule_seed"])
        self.strata = int(mix.get("strata", 256))
        arr = mix["arrivals"]
        if arr["kind"] not in ("closed", "poisson"):
            raise ValueError(f"unknown arrival kind {arr['kind']!r}")
        self.rate = float(arr["rate_per_s"]) if arr["kind"] == "poisson" \
            else None
        self._blocks: dict[int, dict] = {}
        self._due = [0.0]             # due time of request i at [i + 1]
        self._warm = self._residuals(batch) if arr["kind"] == "closed" \
            else []

    def _u(self, block: int, tag: int, n: int | None = None) -> np.ndarray:
        n = n or self.strata
        rng = np.random.default_rng([self.order_seed, block, tag])
        return (rng.permutation(n) + 0.5) / n

    def _residuals(self, n: int, grid: int = 4096) -> list[int]:
        """Remaining output lengths of the ``n`` requests a closed backlog's
        slots hold at steady state (see the module doc)."""
        if n <= 0:
            return []
        dist = self.mix["output_tokens"]
        lens = np.array([quantile(dist, (k + 0.5) / grid)
                         for k in range(grid)], np.float64)
        cum = np.cumsum(lens) / lens.sum()
        life = lens[np.minimum(np.searchsorted(cum, self._u(0, 4, n)),
                               grid - 1)]
        return [max(1, math.ceil(u * x))
                for u, x in zip(self._u(0, 5, n), life)]

    def _block(self, k: int) -> dict:
        if k not in self._blocks:
            self._blocks[k] = {
                "prompt": [quantile(self.mix["prompt_tokens"], u)
                           for u in self._u(k, 1)],
                "out": [quantile(self.mix["output_tokens"], u)
                        for u in self._u(k, 2)],
                "gap": ([-math.log(1.0 - u) / self.rate for u in self._u(k, 3)]
                        if self.rate else None)}
        return self._blocks[k]

    def due(self, i: int) -> float | None:
        if self.rate is None:
            return None
        while len(self._due) <= i + 1:
            j = len(self._due) - 1
            blk = self._block(j // self.strata)
            self._due.append(self._due[-1] + blk["gap"][j % self.strata])
        return self._due[i + 1]

    def get(self, i: int) -> Request:
        blk = self._block(i // self.strata)
        n = blk["prompt"][i % self.strata]
        rng = np.random.default_rng([self.seed, i, 0])
        prompt = rng.integers(0, self.vocab, n).tolist()
        out = self._warm[i] if i < len(self._warm) \
            else blk["out"][i % self.strata]
        return Request(i, prompt, out, self.due(i))
