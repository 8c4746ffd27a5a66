"""YCSB core-workload traffic over a SOSD-like key set, from a seed.

Copies of what the write cell needs, so the yardstick cannot move with the
program:

* the ``osm`` key set: dense clusters of ~800 keys split by huge jumps,
  the generator of the program's ``data/datasets.py`` (SOSD osm cell ids,
  Marcus et al., VLDB 2020);
* YCSB's zipfian request distribution (Cooper et al., SoCC 2010): Gray et
  al.'s bounded zipfian over ranks (SIGMOD 1994, as YCSB's
  ``ZipfianGenerator``), each rank scattered over the key space by 64-bit
  FNV-1a modulo the key count, as YCSB's ``ScrambledZipfianGenerator``
  does;
* the operation mix: each operation independently a read or an update by
  the workload's proportions (YCSB's ``DiscreteGenerator``).
"""
from __future__ import annotations

from typing import List, NamedTuple

import numpy as np

FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211


# ---------------------------------------------------------------------------
# Key set
# ---------------------------------------------------------------------------

def osm_keys(n: int, seed: int) -> np.ndarray:
    """Sorted distinct uint64 keys: clusters of ~800 keys with gaps of 1..3
    and Pareto(0.8) jumps between them."""
    n = int(n)
    rng = np.random.default_rng(seed)
    n_clusters = max(2, n // 800)
    boundaries = np.sort(rng.choice(n - 1, size=n_clusters, replace=False))
    gaps = rng.integers(1, 4, size=n).astype(np.float64)
    jumps = rng.pareto(a=0.8, size=n_clusters) * 1e6 + 1e5
    gaps[boundaries] += np.minimum(jumps, 1e13)
    return np.cumsum(np.maximum(gaps.astype(np.uint64), 1)).astype(np.uint64)


# ---------------------------------------------------------------------------
# Request distribution
# ---------------------------------------------------------------------------

def zeta(n: int, theta: float) -> float:
    """sum_{i=1..n} i^-theta."""
    return float(np.sum(np.arange(1, int(n) + 1, dtype=np.float64)
                        ** -float(theta)))


def zipf_ranks(n: int, size: int, theta: float,
               rng: np.random.Generator) -> np.ndarray:
    """Gray et al.'s bounded zipfian: ``size`` ranks in [0, n), rank 0 the
    most popular, Pr(rank i) ~ (i + 1)^-theta."""
    n = int(n)
    zetan = zeta(n, theta)
    alpha = 1.0 / (1.0 - theta)
    eta = ((1.0 - (2.0 / n) ** (1.0 - theta))
           / (1.0 - zeta(2, theta) / zetan))
    u = rng.random(size)
    uz = u * zetan
    ranks = (n * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    ranks = np.where(uz < 1.0 + 0.5 ** theta, 1, ranks)
    ranks = np.where(uz < 1.0, 0, ranks)
    return np.minimum(ranks, n - 1)


def fnv1a_64(values: np.ndarray) -> np.ndarray:
    """YCSB's ``fnvhash64``: FNV-1a over the 8 little-endian bytes of each
    value, then the absolute value of the signed result."""
    v = np.asarray(values, np.int64).astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, np.uint64)
    prime = np.uint64(FNV_PRIME_64)
    with np.errstate(over="ignore"):
        for i in range(8):
            h ^= (v >> np.uint64(8 * i)) & np.uint64(0xFF)
            h *= prime
    negative = h >= np.uint64(1 << 63)
    return np.where(negative, np.uint64(0) - h, h)


def scrambled_zipfian(n: int, size: int, theta: float,
                      rng: np.random.Generator) -> np.ndarray:
    """Positions in [0, n): zipfian ranks scattered by FNV-1a mod n."""
    ranks = zipf_ranks(n, size, theta, rng)
    return (fnv1a_64(ranks) % np.uint64(n)).astype(np.int64)


# ---------------------------------------------------------------------------
# The pool
# ---------------------------------------------------------------------------

class Batch(NamedTuple):
    """One batch of operations in arrival order: ``reads[i]`` says whether
    operation i reads (else it updates), ``positions[i]`` its key's rank."""

    reads: np.ndarray           # (B,) bool
    positions: np.ndarray       # (B,) int64


def make_pool(mix: dict, n: int, seed: int) -> List[Batch]:
    """The mix's batches over ``n`` keys; every seed gives the same count
    and size of batches, and only which operations and keys change."""
    rng = np.random.default_rng([int(seed) % 2**64, 0x5CB])
    bs, batches = int(mix["batch_events"]), int(mix["batches"])
    total = bs * batches
    shares = np.asarray([mix["read_proportion"], mix["update_proportion"]])
    if not np.isclose(shares.sum(), 1.0):
        raise ValueError("read and update proportions must sum to 1")
    reads = rng.random(total) < shares[0]
    pos = scrambled_zipfian(n, total, float(mix["zipfian_constant"]), rng)
    return [Batch(reads[a:a + bs], pos[a:a + bs])
            for a in range(0, total, bs)]
