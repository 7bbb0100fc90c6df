"""The one general traffic generator: a pure function of `--seed` and the
cell's `traffic` parameters.

Every seed is given the SAME set of request sizes (and, for timed arrivals,
the same set of gaps) in another order: sizes are the quantiles of the
cell's length distributions, not draws from them, so no seed gets a heavier
mix than another. The seed decides the order, the token ids and each
request's sampling seed.

  prompt_tokens / answer_tokens  {"dist": "uniform" | "log_uniform",
                                  "low": a, "high": b}   (inclusive)
  pool         how many (prompt, answer) pairs one cycle holds
  arrival      {"kind": "closed", "clients": n, "stagger_s": s}
               {"kind": "poisson", "rate": requests/s}
  sharing      {"kind": "none"} | {"kind": "prefix", "tokens": k, "groups": g}
               (the first k tokens of a prompt are one of g seeded prefixes)
  sampling     passed to /generate as given (temperature, top_k, top_p)
"""

from __future__ import annotations

import math

import numpy as np

_PAIRING_SEED = 20260928  # pairs prompt and answer quantiles; never a run's seed


def quantile_lengths(spec: dict, n: int) -> list:
    """`n` lengths at the quantiles (i + 0.5) / n of the distribution."""
    low, high = int(spec["low"]), int(spec["high"])
    if not 1 <= low <= high:
        raise ValueError(f"bad length range {spec}")
    qs = (np.arange(n) + 0.5) / n
    if spec["dist"] == "uniform":
        values = low + qs * (high + 1 - low)
        return [min(high, int(v)) for v in values]
    if spec["dist"] == "log_uniform":
        values = low * (high / low) ** qs
        return [min(high, max(low, int(round(v)))) for v in values]
    raise ValueError(f"unknown distribution {spec['dist']!r}")


def size_pool(traffic: dict) -> list:
    """The cycle's `(prompt tokens, answer tokens)` pairs: the same for
    every seed. Answers are paired with prompts by a fixed shuffle, so the
    two lengths are independent without being drawn."""
    n = int(traffic["pool"])
    prompts = quantile_lengths(traffic["prompt_tokens"], n)
    answers = quantile_lengths(traffic["answer_tokens"], n)
    order = np.random.RandomState(_PAIRING_SEED).permutation(n)
    return [(prompts[i], answers[int(j)]) for i, j in enumerate(order)]


class Stream:
    """The run's requests, by index: `request(i)` is the i-th request sent
    (whichever client sends it), `due(i)` its arrival time in a timed
    arrival process."""

    def __init__(self, traffic: dict, seed: int, vocab: int):
        self.traffic = traffic
        self.seed = int(seed) % (2 ** 32)
        self.vocab = int(vocab)
        self.pool = size_pool(traffic)
        self.sampling = dict(traffic.get("sampling", {}))
        self.sharing = dict(traffic.get("sharing", {"kind": "none"}))
        self._orders = {}
        self._gaps = None
        self._dues = {}
        if self.sharing["kind"] == "prefix":
            rng = np.random.RandomState([self.seed, 0x5AFE])
            self._prefixes = rng.randint(
                1, self.vocab, (int(self.sharing["groups"]),
                                int(self.sharing["tokens"])))

    def _order(self, cycle: int):
        if cycle not in self._orders:
            self._orders[cycle] = np.random.RandomState(
                [self.seed, 0x0DE2, cycle]).permutation(len(self.pool))
        return self._orders[cycle]

    def sizes(self, i: int):
        cycle, k = divmod(i, len(self.pool))
        return self.pool[int(self._order(cycle)[k])]

    def request(self, i: int) -> dict:
        n_prompt, n_answer = self.sizes(i)
        rng = np.random.RandomState([self.seed, 0x70C5, i])
        ids = rng.randint(1, self.vocab, n_prompt)
        if self.sharing["kind"] == "prefix":
            k = min(n_prompt, self._prefixes.shape[1])
            ids[:k] = self._prefixes[i % len(self._prefixes), :k]
        payload = {"prompt_ids": [int(t) for t in ids],
                   "n_steps": int(n_answer),
                   "seed": int(rng.randint(0, 2 ** 31 - 1))}
        payload.update(self.sampling)
        return payload

    def due(self, i: int) -> float:
        """Seconds after the start of load at which request `i` is due
        (Poisson arrivals: exponential gaps at the quantiles, in the
        seed's order, so every seed offers the same load)."""
        arrival = self.traffic["arrival"]
        if arrival["kind"] != "poisson":
            raise ValueError("only a timed arrival process has due times")
        n = len(self.pool)
        if self._gaps is None:
            qs = (np.arange(n) + 0.5) / n
            self._gaps = -np.log1p(-qs) / float(arrival["rate"])
        cycle, k = divmod(i, n)
        if cycle not in self._dues:
            order = np.random.RandomState(
                [self.seed, 0xA221, cycle]).permutation(n)
            self._dues = {cycle: np.cumsum(self._gaps[order])}  # one cycle kept
        return cycle * float(self._gaps.sum()) + float(self._dues[cycle][k])


def longest_request(traffic: dict) -> int:
    """Most tokens (prompt + answer) a request of this traffic can hold."""
    return max(p + a for p, a in size_pool(traffic))


def pages_for(traffic: dict, slots: int, page: int) -> int:
    """A pool as large as the cell's slots can fill: slots x
    ceil((longest prompt + longest answer) / page), plus the sink page."""
    longest = (int(traffic["prompt_tokens"]["high"])
               + int(traffic["answer_tokens"]["high"]))
    return slots * math.ceil(longest / page) + 1
