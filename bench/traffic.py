"""Key sets and traffic, generated from a seed by one general generator.

A traffic mix is a data file ``traffic/<mix>.json`` of parameters; this
module reads it and makes the mix's pool of trace events.  ``generator``
names the shape of the mix, ``entry`` the session it drives.

Key sets are synthetic stand-ins for the SOSD families (Marcus et al.,
VLDB 2020), drawn from gap distributions of the same character.  The
generators here are copies, so the yardstick cannot move with the
program.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

# popular ranks scatter over the key space through this affine permutation
_ZIPF_MUL = 6364136223846793005
_ZIPF_ADD = 1442695040888963407


# ---------------------------------------------------------------------------
# Key sets
# ---------------------------------------------------------------------------

def _from_gaps(gaps: np.ndarray) -> np.ndarray:
    return np.cumsum(np.maximum(gaps.astype(np.uint64), 1)).astype(np.uint64)


def _books(n: int, rng: np.random.Generator) -> np.ndarray:
    return _from_gaps(np.minimum(rng.lognormal(1.0, 2.0, size=n), 1e9))


KEY_SETS = {"books": _books}


def make_keys(name: str, n: int, seed: int) -> np.ndarray:
    """Sorted distinct uint64 keys of the named family."""
    if name not in KEY_SETS:
        raise ValueError(f"unknown key set {name!r}; one of {sorted(KEY_SETS)}")
    return KEY_SETS[name](int(n), np.random.default_rng(seed))


# ---------------------------------------------------------------------------
# Mixes: each returns the pool's query positions, one array per regime
# ---------------------------------------------------------------------------

def point_mixture(mix: dict, n: int, rng: np.random.Generator
                  ) -> List[np.ndarray]:
    """The paper's point mixture (Table III): hotspot, zipf and uniform
    shares.  Hotspots are contiguous windows of ``hotspot_frac`` of the
    keys each and are drawn anew for every regime; zipf ranks scatter over
    the key space, so they are skew without locality."""
    shares = [mix["mixture"][k] for k in ("hotspot", "zipf", "uniform")]
    width = max(1, int(n * mix["hotspot_frac"]))
    regimes = []
    for _ in range(mix["regimes"]):
        hot, zipf, uni = rng.multinomial(mix["regime_events"], shares)
        starts = rng.integers(0, max(1, n - width), size=mix["n_hotspots"])
        parts = [starts[rng.integers(0, mix["n_hotspots"], size=hot)]
                 + rng.integers(0, width, size=hot)]
        ranks = np.minimum(rng.zipf(mix["zipf_a"], size=zipf).astype(np.int64)
                           - 1, n - 1)
        parts.append(((ranks * _ZIPF_MUL + _ZIPF_ADD) % n).astype(np.int64))
        parts.append(rng.integers(0, n, size=uni))
        pos = np.concatenate(parts)
        rng.shuffle(pos)
        regimes.append(pos.astype(np.int64))
    return regimes


GENERATORS = {"point_mixture": point_mixture}


def make_pool(mix: dict, keys: np.ndarray, seed: int
              ) -> Dict[str, List[np.ndarray]]:
    """The mix's event pool as key arrays: ``{"point": [regime keys...]}``.

    Every seed gives the same sizes; only which keys, and their order,
    change with it.
    """
    gen = GENERATORS[mix["generator"]]
    rng = np.random.default_rng([int(seed) % 2**64, 0x7EA])
    positions = gen(mix, int(keys.shape[0]), rng)
    return {"point": [keys[p] for p in positions]}
