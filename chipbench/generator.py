"""The one traffic generator: sessions and their requests from a traffic
file's parameters and the seed.

Every seed gets the same set of prompt and answer lengths, in another
order: lengths sit on a fixed log-uniform grid (the ``(i + 0.5) / n``
quantiles for ``n`` sessions), and the seed picks which
session starts where on it.  So no seed brings a prompt length, and with
it a prefill shape, that another seed has not compiled, and set-up does
the same work on every seed.  The seed also draws every token id.

A session is a closed loop: when its request finishes it sends the next
one at once, stepping one place further along both grids.
"""
from __future__ import annotations

import math

import numpy as np


def grid(spec: dict, n: int) -> list[int]:
    """``n`` lengths at the ``(i + 0.5) / n`` quantiles of the
    log-uniform range ``{"min", "max"}``."""
    lo, hi = math.log(spec["min"]), math.log(spec["max"])
    return [round(math.exp(lo + (i + 0.5) / n * (hi - lo)))
            for i in range(n)]


class Traffic:
    def __init__(self, spec: dict, seed: int, vocab: int) -> None:
        self.spec = spec
        self.seed = int(seed)
        self.vocab = vocab
        n = spec["sessions"]
        self.prompts = grid(spec["prompt_tokens"], n)
        self.answers = grid(spec["answer_tokens"], n)
        rng = np.random.default_rng([self.seed, 0])
        self._p0 = rng.permutation(n)
        self._a0 = rng.permutation(n)

    @property
    def sessions(self) -> int:
        return self.spec["sessions"]

    def max_tokens(self) -> int:
        """The longest prompt plus answer any request can reach."""
        return self.spec["prompt_tokens"]["max"] + \
            self.spec["answer_tokens"]["max"]

    def request(self, session: int, turn: int) -> tuple[list[int], int]:
        """(prompt token ids, answer length) of a session's ``turn``-th
        request."""
        n = self.sessions
        plen = self.prompts[(self._p0[session] + turn) % n]
        alen = self.answers[(self._a0[session] + turn) % n]
        rng = np.random.default_rng([self.seed, 1, session, turn])
        return rng.integers(0, self.vocab, size=plen).tolist(), alen
