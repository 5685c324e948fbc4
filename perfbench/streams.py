"""Seeded streams and request schedules.

The class model is a copy of ``repro.data.gmm_streams.gaussian_classes``
(class means N(0, sep²), per-dimension scales U(0.5, 1.5), unit-normal
noise), kept here so that no change to the program moves the traffic.
Points are drawn in blocks, each from its own generator keyed by
(seed, stream, block), so a block's content does not depend on how many
blocks a run consumed before it.  A point is the joint vector
[features ‖ one-hot(label)], as the paper's classifier learns it.
"""
from __future__ import annotations

import dataclasses

import numpy as np

#: stream tags: one generator family per use
POOL, SIGMA, INGEST, READ, SCHEDULE, SNAPSHOT = range(6)


def seeded(seed: int, *key: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2 ** 64, *key])


@dataclasses.dataclass(frozen=True)
class ClassModel:
    means: np.ndarray       # (classes, features)
    scales: np.ndarray      # (classes, features)

    @property
    def features(self) -> int:
        return int(self.means.shape[1])

    @property
    def classes(self) -> int:
        return int(self.means.shape[0])

    @property
    def dim(self) -> int:
        return self.features + self.classes


def class_model(seed: int, features: int, classes: int,
                sep: float = 3.0) -> ClassModel:
    rng = seeded(seed)
    means = rng.normal(0, sep, (classes, features))
    scales = rng.uniform(0.5, 1.5, (classes, features))
    return ClassModel(means, scales)


def draw(model: ClassModel, seed: int, n: int, *key: int) -> np.ndarray:
    """(n, dim) float32 joint points of block ``key``."""
    rng = seeded(seed, *key)
    y = rng.integers(0, model.classes, n)
    x = model.means[y] + rng.normal(0, 1, (n, model.features)) \
        * model.scales[y]
    onehot = np.eye(model.classes)[y]
    return np.concatenate([x, onehot], axis=1).astype(np.float32)


def sigma_ini(model: ClassModel, seed: int, delta: float,
              n: int = 1024) -> np.ndarray:
    """(dim,) eq. 13 σ_ini = δ·std over a seeded sample of the stream; the
    label block takes 1, the estimate ``repro.core.head`` uses."""
    x = draw(model, seed, n, SIGMA)[:, :model.features]
    std = x.std(axis=0)
    std = np.where(std <= 1e-12, 1.0, std)
    return np.concatenate([delta * std,
                           np.ones(model.classes)]).astype(np.float32)


def ingest_chunk(model: ClassModel, seed: int, chunk: int,
                 i: int) -> np.ndarray:
    return draw(model, seed, chunk, INGEST, i)


def poisson_schedule(seed: int, rate: float, seconds: float,
                     kinds: dict) -> tuple:
    """Open-loop arrivals: (due offsets in s (n,), kind index (n,)).

    Every seed gets the same number of requests, the same multiset of
    exponential gaps (their quantiles at rate ``rate``) and the same count
    of each kind; the seed only orders them.  So runs with different
    seeds do the same work, in another order."""
    n = max(int(round(rate * seconds)), 1)
    rng = seeded(seed, SCHEDULE)
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q) / rate)
    due = np.cumsum(gaps)
    shares = np.asarray(list(kinds.values()), float)
    counts = np.floor(shares / shares.sum() * n).astype(int)
    counts[0] += n - counts.sum()
    kind = rng.permutation(np.repeat(np.arange(len(counts)), counts))
    return due, kind
