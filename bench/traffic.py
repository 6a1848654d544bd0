"""The one traffic generator: a closed loop of clients.  A traffic mix is
a data file, ``bench/traffic/<name>.json``:

    {"clients": 8,
     "pool": 40,
     "prompt_len": {"choices": [8, 16], "weights": [0.6, 0.4]}
                 | {"lognormal": {"median": 200, "sigma": 0.8},
                    "min": 16, "max": 1024},
     "output_len": (same forms),
     "source": "...", "why": "..."}

Each client sends a request, waits for its answer and sends the next.
The seed changes the token ids and which client serves which stream,
never the work itself: the pool holds ``pool`` (prompt, output) length
pairs fixed by the file (choices by their weights, lognormals by their
quantiles), paired by one fixed shuffle and ordered so that every
stretch of it holds the mix.  Stream s of C sends pool entries s, s + C,
s + 2C, ... in turn, and the seed deals the streams to the clients.

The loop starts staggered: the first request of stream s, admitted
before the window, keeps only ceil(n (s + 1/2) / C) of the n tokens of
its answer, so the clients finish their first requests at spread-out
steps, as in a loop that has run for a while, and the window does not
open on C requests that all started together.  Clients are alike, so
every seed serves the same requests at the same steps.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

_PAIRING_SEED = 0x5EED


def _largest_remainder(weights, n: int) -> list:
    w = np.asarray(weights, float) / float(np.sum(weights))
    raw = w * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def lengths(spec: dict, n: int) -> list:
    """``n`` lengths, ascending, that follow ``spec`` exactly."""
    if "choices" in spec:
        counts = _largest_remainder(spec["weights"], n)
        return sorted(int(c) for c, k in zip(spec["choices"], counts)
                      for _ in range(k))
    ln = spec["lognormal"]
    mu, sigma = math.log(ln["median"]), ln["sigma"]
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    out = [min(max(round(math.exp(mu + sigma * q)), spec["min"]),
               spec["max"]) for q in z]
    return sorted(int(x) for x in out)


def _max_len(spec: dict) -> int:
    return int(max(spec["choices"]) if "choices" in spec else spec["max"])


@dataclasses.dataclass
class Draw:
    prompt: np.ndarray
    max_new: int


class Traffic:
    """Requests of one mix under one seed."""

    def __init__(self, spec: dict, seed: int, vocab: int):
        self.spec = spec
        self.clients = int(spec["clients"])
        n = int(spec["pool"])
        prompts = lengths(spec["prompt_len"], n)
        outputs = lengths(spec["output_len"], n)
        perm = np.random.default_rng(_PAIRING_SEED).permutation(n)
        pairs = [(p, outputs[j]) for p, j in zip(prompts, perm)]
        # every stretch of the pool holds the mix: the i-th of the n_p
        # prompts of length p sits at (i + 1/2) / n_p of the way through
        rank, count = {}, {}
        for p, _ in pairs:
            count[p] = count.get(p, 0) + 1
        keyed = []
        for p, out in pairs:
            i = rank[p] = rank.get(p, -1) + 1
            keyed.append(((i + 0.5) / count[p], -p, out, p))
        self.pool = [(p, out) for _, _, out, p in sorted(keyed)]
        self.vocab = int(vocab)
        self._rng = np.random.default_rng(int(seed))
        self._stream = list(self._rng.permutation(self.clients))
        self._sent = [0] * self.clients

    def prompt_lengths(self) -> list:
        """Every prompt length the mix sends (each is its own prefill
        shape, warmed up before the window)."""
        return sorted({p for p, _ in self.pool})

    def max_len(self) -> int:
        """The longest prompt plus the longest output."""
        return _max_len(self.spec["prompt_len"]) + \
            _max_len(self.spec["output_len"])

    def next(self, client: int) -> Draw:
        """The next request of ``client``'s stream."""
        s = self._stream[client]
        k = self._sent[client]
        self._sent[client] += 1
        p, out = self.pool[(s + self.clients * k) % len(self.pool)]
        if k == 0:
            out = math.ceil(out * (s + 0.5) / self.clients)
        toks = self._rng.integers(0, self.vocab, size=p, dtype=np.int32)
        return Draw(prompt=toks, max_new=int(out))
