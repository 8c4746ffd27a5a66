"""``workload.locate``: ranks in the key file's own dtype.

Every key dtype, needle kind and needle order is checked against
``bisect.bisect_left`` over Python numbers (exact for ints and floats
alike), clamped to ``n - 1``; NaN sorts after every key, as in NumPy's
order.  A traced call counts ``locate.native`` under ``workload.locate``
when the search ran in the key file's dtype, and only then.
"""
import bisect
import math

import jax
import numpy as np
import pytest

from repro import obs
from repro.core.workload import locate

N_KEYS = 300
N_NEEDLES = 64
U64_MAX = np.iinfo(np.uint64).max


def _keys(dtype: str) -> np.ndarray:
    rng = np.random.default_rng(3)
    if dtype == "float64":
        return np.unique(rng.uniform(-1e6, 1e6, N_KEYS))
    lo = 0 if dtype == "uint64" else -10**6
    return np.unique(rng.integers(lo, 10**6, N_KEYS)).astype(dtype)


def _needles(keys: np.ndarray, kind: str) -> np.ndarray:
    """``N_NEEDLES`` needles of ``kind`` for ``keys``: about half of them
    keys, the rest between, below or above them."""
    rng = np.random.default_rng(5)
    hits = keys[rng.integers(0, keys.shape[0], N_NEEDLES // 2)]
    span = [float(keys[0]) - 10.0, float(keys[-1]) + 10.0]
    between = rng.uniform(*span, N_NEEDLES - hits.shape[0])
    mid = np.concatenate([hits.astype(np.float64), between])
    if kind == "same":
        if keys.dtype.kind != "f":
            between = np.abs(np.round(between))
        return np.concatenate([hits, between.astype(keys.dtype)])
    if kind in ("int64", "uint64"):
        vals = np.round(mid)
        if kind == "uint64":
            vals = np.abs(vals)
        return vals.astype(kind)
    if kind == "float_integral":
        return np.round(mid)
    if kind == "float_fractional":
        return np.floor(mid) + rng.choice([0.25, 0.5, 0.75], mid.shape[0])
    if kind == "negative":
        if keys.dtype == np.float64:
            tail = np.array([-np.inf, -1e300, -1.5e6])
            return np.concatenate([mid[:-3], tail])
        tail = np.array([np.iinfo(np.int64).min, -(2**40), -1, 0])
        return np.concatenate([np.round(mid[:-4]).astype(np.int64), tail])
    if kind == "beyond":
        if keys.dtype == np.float64:
            tail = np.array([np.inf, 1e300, 1.5e6])
            return np.concatenate([mid[:-3], tail])
        # uint64 needles past both integer key dtypes' tops
        tail = np.array([U64_MAX, 2**63, 2**63 - 1, 10**7], np.uint64)
        return np.concatenate([np.abs(np.round(mid[:-4])).astype(np.uint64),
                               tail])
    if kind == "float_beyond":
        tail = np.array([np.inf, 1e30, 2.0**64, 2.0**63, -2.0**63, -1e30,
                         -np.inf])
        return np.concatenate([mid[:-7], tail])
    if kind == "nan":
        out = np.round(mid) + 0.5
        out[::7] = np.nan
        return out
    raise ValueError(kind)


def _ordered(needles: np.ndarray, order: str) -> np.ndarray:
    rng = np.random.default_rng(11)
    if order == "ascending":
        return np.sort(needles)
    if order == "random":
        return rng.permutation(needles)
    if order == "duplicates":
        return rng.permutation(np.concatenate([needles, needles[::3],
                                               needles[:5]]))
    if order == "empty":
        return needles[:0]
    if order == "single":
        return needles[len(needles) // 2:len(needles) // 2 + 1]
    raise ValueError(order)


def _expected(keys: np.ndarray, needles: np.ndarray) -> np.ndarray:
    ks = keys.tolist()
    n = len(ks)
    out = [n if isinstance(q, float) and math.isnan(q)
           else bisect.bisect_left(ks, q) for q in needles.tolist()]
    return np.minimum(np.asarray(out, np.int64), n - 1)


@pytest.mark.parametrize("order", ["ascending", "random", "duplicates",
                                   "empty", "single"])
@pytest.mark.parametrize("kind", ["same", "int64", "uint64",
                                  "float_integral", "float_fractional",
                                  "negative", "beyond", "float_beyond",
                                  "nan"])
@pytest.mark.parametrize("key_dtype", ["uint64", "int64", "float64"])
def test_ranks_match_bisect(key_dtype, kind, order):
    keys = _keys(key_dtype)
    needles = _ordered(_needles(keys, kind), order)
    got = locate(keys, needles)
    assert got.dtype == np.int64
    assert got.shape == needles.shape
    np.testing.assert_array_equal(got, _expected(keys, needles))


@pytest.mark.parametrize("needle_dtype", ["uint64", "int64", "float64"])
def test_uint64_keys_above_2_53_rank_exactly(needle_dtype):
    """Consecutive keys past 2**53 collapse in float64; in their own dtype
    each ranks where ``bisect`` puts it."""
    keys = (np.uint64(2**60) + np.arange(1000, dtype=np.uint64) * 3)
    rng = np.random.default_rng(2)
    needles = keys[rng.permutation(1000)[:200]]
    needles[::2] += np.uint64(1)        # between two keys
    if needle_dtype == "float64":
        # floats this large are integers: exact needles the floats can hold
        needles = needles.astype(np.float64)
    else:
        needles = needles.astype(needle_dtype)
    want = _expected(keys, needles)
    np.testing.assert_array_equal(locate(keys, needles), want)
    if needle_dtype != "float64":
        common = np.minimum(np.searchsorted(keys.astype(np.float64),
                                            needles.astype(np.float64)),
                            keys.shape[0] - 1)
        assert (common != want).any()    # what the float64 search gave


#: (key dtype, needles, whether the search runs in the key file's dtype)
TRACED_CALLS = {
    "uint64_keys_int64_needles": ("uint64", np.array([5, -3, 70], np.int64),
                                  True),
    "int64_keys_uint64_needles": ("int64", np.array([2**63, 4], np.uint64),
                                  True),
    "uint64_keys_float_needles": ("uint64", np.array([2.5, 9.0]), True),
    "float_keys_float_needles": ("float64", np.array([0.5, -2.0]), True),
    "uint64_keys_nan_needles": ("uint64", np.array([np.nan, 1.0]), False),
    "float_keys_int64_needles": ("float64", np.array([3, 1], np.int64),
                                 False),
}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Each call of ``TRACED_CALLS`` once under one profiler trace: its
    ranks, and the names of the counts under its ``workload.locate``."""
    out = tmp_path_factory.mktemp("trace")
    obs.clear()
    ranks = {}
    with jax.profiler.trace(str(out)):
        for name, (dtype, needles, _) in TRACED_CALLS.items():
            ranks[name] = (locate(_keys(dtype), needles),
                           _expected(_keys(dtype), needles))
    reg = obs.snapshot()
    obs.clear()
    spans = reg["spans"]
    assert [s[0] for s in spans] == ["workload.locate"] * len(TRACED_CALLS)
    counts = {name: [c[0] for c in reg["counts"] if c[3] == i]
              for i, name in enumerate(TRACED_CALLS)}
    return ranks, counts


@pytest.mark.parametrize("name", list(TRACED_CALLS))
def test_traced_call_counts_native_searches_only(traced, name):
    ranks, counts = traced
    native = TRACED_CALLS[name][2]
    assert counts[name] == (["locate.native"] if native else [])
    got, want = ranks[name]
    np.testing.assert_array_equal(got, want)
