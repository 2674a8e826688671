"""The one general traffic generator: it reads a mix file
(``bench/traffic/<mix>.json``) and the run's seed.

A mix file holds:

- ``loop``: ``"closed"`` — ``clients`` callers, each sending its next
  request as soon as the last one returns (its due time is its send
  time) — or ``"open"`` — requests due at seeded exponential gaps of mean
  ``1 / rate_per_s``.
- ``mix``: ``[{"pool": name, "share": weight, ...pool params}]``; a
  rejection pool takes ``max_trials_per_expected`` (the trial budget in
  multiples of the catalog's E[trials]).

Every request's seed, pool and due time come from ``--seed`` alone.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, List, Optional

import numpy as np

#: independent streams drawn from one run seed
STREAM_REQUESTS, STREAM_ARRIVALS, STREAM_WARMUP = 11, 12, 13


@dataclasses.dataclass
class Request:
    rid: int
    pool: str
    seed: int
    due: float = 0.0
    submit: Optional[float] = None
    done: Optional[float] = None
    trials: int = 0
    accepted: bool = False
    items: Optional[np.ndarray] = None
    error: Optional[str] = None


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


class Traffic:
    """Seeded request stream of one mix."""

    def __init__(self, spec: dict, seed: int):
        self.spec = spec
        self.loop = spec["loop"]
        if self.loop not in ("closed", "open"):
            raise ValueError(f"unknown loop {self.loop!r}")
        self.mix = spec["mix"]
        share = np.array([float(e["share"]) for e in self.mix])
        self._p = share / share.sum()
        self._rng = _rng(seed, STREAM_REQUESTS)
        self._arr = _rng(seed, STREAM_ARRIVALS)
        self._warm = _rng(seed, STREAM_WARMUP)
        self._rid = 0

    @property
    def pools(self) -> List[str]:
        return [e["pool"] for e in self.mix]

    def pool_params(self, pool: str) -> dict:
        return next(e for e in self.mix if e["pool"] == pool)

    def next(self, due: float = 0.0) -> Request:
        """The next request of the stream."""
        i = int(self._rng.choice(len(self.mix), p=self._p)) \
            if len(self.mix) > 1 else 0
        seed = int(self._rng.integers(0, 2 ** 31 - 1))
        self._rid += 1
        return Request(rid=self._rid, pool=self.mix[i]["pool"], seed=seed,
                       due=due)

    def warmup_seeds(self, n: int) -> List[int]:
        return [int(s) for s in self._warm.integers(0, 2 ** 31 - 1, size=n)]

    def due_times(self, t_open: float, t_close: float) -> Iterator[float]:
        """Open loop: due times in [t_open, t_close)."""
        rate = float(self.spec["rate_per_s"])
        t = t_open
        while True:
            t += float(self._arr.exponential(1.0 / rate))
            if t >= t_close:
                return
            yield t
