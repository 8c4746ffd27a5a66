"""Plain reference of the semantics the served path computes, in float64.

Straight NumPy from the paper's definitions; it imports nothing of the
program under test and takes nothing the program has made.  It builds its
own indexes from the key set, locates the traffic itself and prices from
its own histograms.

* locate: the rank of each query key in the sorted key file;
* RMI: a two-layer RMI with per-leaf linear models and per-leaf maximal
  error (Kraska et al., SIGMOD 2018), error bounds rounded up to powers
  of two as the paper's section V-C groups them;
* occupancy: the expected page-reference histogram of point lookups
  (paper Eq. 12/13) with per-query error bounds, and E[DAC] under the
  all-at-once fetch (Lemma III.2, mixed over the routed leaves);
* pricing: Che's approximation for LRU (Eq. 7/8), the compulsory closed
  form when the buffer holds every referenced page;
* the drift summary: total-variation distance of 32-bin page popularity.

``lut_round`` lets the control run the same pipeline with the Eq. 12
weights rounded to a lower precision.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

PAGE_BINS = 32
BISECT_ITERS = 100


def locate(keys: np.ndarray, query_keys: np.ndarray) -> np.ndarray:
    pos = np.searchsorted(keys, np.asarray(query_keys), side="left")
    return np.minimum(pos, keys.shape[0] - 1).astype(np.int64)


def num_pages(n: int, c_ipp: int) -> int:
    return -(-int(n) // int(c_ipp))


def capacity(budget_bytes: float, index_bytes: float, page_bytes: int) -> int:
    """Buffer pages left once the index is resident."""
    return int(max(0, (budget_bytes - index_bytes) // page_bytes))


# ---------------------------------------------------------------------------
# RMI
# ---------------------------------------------------------------------------

def rmi_size_bytes(branch: int) -> int:
    """Root (slope, intercept) plus per-leaf slope, intercept and bound."""
    return 16 + 24 * int(branch)


@dataclasses.dataclass(frozen=True)
class RMI:
    root_slope: float
    root_intercept: float
    branch: int
    leaf_slope: np.ndarray
    leaf_intercept: np.ndarray
    leaf_x0: np.ndarray
    leaf_eps: np.ndarray
    n: int

    def route(self, query_keys: np.ndarray) -> np.ndarray:
        pos = self.root_slope * np.asarray(query_keys).astype(np.float64) \
            + self.root_intercept
        leaf = np.floor(pos * self.branch / max(self.n, 1)).astype(np.int64)
        return np.clip(leaf, 0, self.branch - 1)

    def predict(self, query_keys: np.ndarray, leaf: np.ndarray) -> np.ndarray:
        dx = np.asarray(query_keys).astype(np.float64) - self.leaf_x0[leaf]
        pred = self.leaf_slope[leaf] * dx + self.leaf_intercept[leaf]
        return np.clip(np.floor(pred), 0, self.n - 1).astype(np.int64)


def build_rmi(keys: np.ndarray, branch: int) -> RMI:
    """Least-squares root over all keys, least-squares leaves over the keys
    the root routes to each leaf; a leaf's bound is its largest error."""
    n = keys.shape[0]
    kf = keys.astype(np.float64)
    ranks = np.arange(n, dtype=np.float64)
    kc = kf - kf.mean()
    denom = float((kc * kc).sum())
    root_slope = float((kc * ranks).sum() / denom) if denom > 0 else 0.0
    root_intercept = float(ranks.mean() - root_slope * kf.mean())
    leaf = np.clip(np.floor((root_slope * kf + root_intercept) * branch / n)
                   .astype(np.int64), 0, branch - 1)
    cnt = np.bincount(leaf, minlength=branch).astype(np.float64)
    first = np.searchsorted(leaf, np.arange(branch), side="left")
    x0 = kf[np.clip(first, 0, n - 1)]
    xc = kf - x0[leaf]
    sx = np.bincount(leaf, weights=xc, minlength=branch)
    sy = np.bincount(leaf, weights=ranks, minlength=branch)
    sxx = np.bincount(leaf, weights=xc * xc, minlength=branch)
    sxy = np.bincount(leaf, weights=xc * ranks, minlength=branch)
    denom = cnt * sxx - sx * sx
    safe = denom > 1e-30
    slope = np.where(safe, (cnt * sxy - sx * sy) / np.where(safe, denom, 1.0),
                     0.0)
    intercept = np.where(cnt > 0, (sy - slope * sx) / np.maximum(cnt, 1.0),
                         0.0)
    if (cnt == 0).any():
        # an empty leaf answers with its nearest populated leaf's line
        populated = np.flatnonzero(cnt > 0)
        nearest = populated[np.clip(np.searchsorted(populated,
                                                    np.arange(branch)),
                                    0, populated.size - 1)]
        slope = np.where(cnt > 0, slope, slope[nearest])
        intercept = np.where(cnt > 0, intercept, intercept[nearest])
        x0 = np.where(cnt > 0, x0, x0[nearest])
    rmi = RMI(root_slope, root_intercept, int(branch), slope, intercept, x0,
              np.zeros(branch, np.int64), int(n))
    err = np.abs(rmi.predict(keys, leaf) - np.arange(n, dtype=np.int64))
    # the root is monotone on sorted keys, so each leaf owns one run
    starts = np.flatnonzero(np.r_[True, leaf[1:] != leaf[:-1]])
    eps = np.zeros(branch, np.int64)
    eps[leaf[starts]] = np.maximum.reduceat(err, starts)
    return dataclasses.replace(rmi, leaf_eps=np.maximum(eps, 1))


def pow2_ceil(eps: np.ndarray) -> np.ndarray:
    eps = np.maximum(np.asarray(eps, np.int64), 1)
    return (2 ** np.ceil(np.log2(eps))).astype(np.int64)


# ---------------------------------------------------------------------------
# Occupancy (Eq. 12/13)
# ---------------------------------------------------------------------------

def lut_radius(eps: int, c_ipp: int) -> int:
    return int(np.ceil(2 * eps / c_ipp))


def point_lut(eps: int, c_ipp: int) -> np.ndarray:
    """(c_ipp, 2D+1): Pr(page q+d read | true offset s in page q) for a
    window of +-eps around a prediction uniform in +-eps (Eq. 12)."""
    radius = lut_radius(eps, c_ipp)
    s = np.arange(c_ipp)[:, None]
    d = np.arange(-radius, radius + 1)[None, :] * c_ipp
    lo = np.maximum(-eps, d - s - eps)
    hi = np.minimum(eps, d - s + c_ipp - 1 + eps)
    return np.maximum(0, hi - lo + 1) / float(2 * eps + 1)


def point_histogram(positions: np.ndarray, eps_q: np.ndarray, c_ipp: int,
                    pages: int,
                    lut_round: Optional[Callable] = None) -> np.ndarray:
    """Expected references per page; window mass off the file is dropped."""
    counts = np.zeros(pages, np.float64)
    page, slot = np.divmod(np.asarray(positions, np.int64), c_ipp)
    for eps in np.unique(eps_q):
        sel = eps_q == eps
        lut = point_lut(int(eps), c_ipp)
        if lut_round is not None:
            lut = lut_round(lut)
        radius = lut_radius(int(eps), c_ipp)
        target = page[sel, None] + np.arange(-radius, radius + 1)[None, :]
        weight = lut[slot[sel]]
        ok = (target >= 0) & (target < pages)
        counts += np.bincount(target[ok], weights=weight[ok], minlength=pages)
    return counts


def page_popularity(positions: np.ndarray, c_ipp: int,
                    pages: int) -> np.ndarray:
    """The drift summary: queries per 1/32 of the page range."""
    bins = np.minimum((positions // c_ipp) * PAGE_BINS // pages,
                      PAGE_BINS - 1)
    return np.bincount(bins, minlength=PAGE_BINS).astype(np.float64)


@dataclasses.dataclass
class BatchProfile:
    """One batch's reference profile over every candidate."""

    positions: np.ndarray          # (Q,)
    counts: np.ndarray             # (K, P)
    dacs: np.ndarray               # (K,) E[DAC] per query
    n_queries: int


class Deployment:
    """Key set, geometry, budget and the RMI candidates of one config."""

    def __init__(self, keys: np.ndarray, branches: Sequence[int], *,
                 c_ipp: int, page_bytes: int, budget_bytes: float,
                 splits: Sequence[float]):
        self.keys = keys
        self.c_ipp = int(c_ipp)
        self.page_bytes = int(page_bytes)
        self.budget = float(budget_bytes)
        self.splits = tuple(splits)
        self.pages = num_pages(keys.shape[0], c_ipp)
        feasible = [b for b in branches
                    if capacity(self.budget, rmi_size_bytes(b),
                                page_bytes) >= 1]
        self.branches = list(feasible)
        self.sizes = np.asarray([rmi_size_bytes(b) for b in feasible],
                                np.float64)
        self.rmis = [build_rmi(keys, b) for b in feasible]

    def profile(self, query_keys: np.ndarray,
                lut_round: Optional[Callable] = None) -> BatchProfile:
        pos = locate(self.keys, query_keys)
        counts, dacs = [], []
        for rmi in self.rmis:
            leaf = rmi.route(query_keys)
            counts.append(point_histogram(pos, pow2_ceil(rmi.leaf_eps[leaf]),
                                          self.c_ipp, self.pages, lut_round))
            w = np.bincount(leaf, minlength=rmi.branch).astype(np.float64)
            w /= max(w.sum(), 1.0)
            dacs.append(float(np.sum(w * (1.0 + 2.0 * rmi.leaf_eps
                                          / self.c_ipp))))
        return BatchProfile(pos, np.stack(counts), np.asarray(dacs),
                            int(pos.shape[0]))

    def table(self) -> List[Tuple[int, int, int]]:
        """(row, knob, capacity) cells of the joint knob x split search:
        each knob's largest buffer first, then every split fraction of the
        budget that leaves room for the index."""
        cells = []
        for i, (b, size) in enumerate(zip(self.branches, self.sizes)):
            cap_max = capacity(self.budget, size, self.page_bytes)
            cells.append((i, b, cap_max))
            for f in self.splits:
                c = int(f * self.budget // self.page_bytes)
                if 1 <= c < cap_max:
                    cells.append((i, b, c))
        return cells


# ---------------------------------------------------------------------------
# Pricing (Eq. 7/8)
# ---------------------------------------------------------------------------

def lru_hit_rates(counts: np.ndarray, caps: np.ndarray) -> np.ndarray:
    """Che hit rate of each (histogram row, capacity) pair, one row each."""
    counts = np.asarray(counts, np.float64)
    caps = np.asarray(caps, np.int64)
    totals = counts.sum(axis=1)
    probs = counts / np.maximum(totals, 1e-300)[:, None]
    distinct = (counts > 0).sum(axis=1)
    h = np.zeros(caps.shape[0], np.float64)
    comp = (caps >= distinct) & (totals > 0)
    h[comp] = (totals[comp] - distinct[comp]) / np.maximum(totals[comp], 1.0)
    solve = (caps >= 1) & (caps < distinct) & (totals > 0)
    if solve.any():
        p = probs[solve]
        c = caps[solve].astype(np.float64)[:, None]
        pmin = np.where(p > 0, p, np.inf).min(axis=1, keepdims=True)
        lo = np.zeros_like(c)
        hi = np.maximum(4.0 * c / pmin, 1.0)
        for _ in range(BISECT_ITERS):
            mid = 0.5 * (lo + hi)
            low = (-np.expm1(-p * mid)).sum(axis=1, keepdims=True) < c
            lo = np.where(low, mid, lo)
            hi = np.where(low, hi, mid)
        t = 0.5 * (lo + hi)
        h[solve] = (p * -np.expm1(-p * t)).sum(axis=1)
    return h


def tv_distance(a: np.ndarray, b: np.ndarray) -> float:
    sa, sb = float(a.sum()), float(b.sum())
    if sa == 0 and sb == 0:
        return 0.0
    pa = a / sa if sa > 0 else a
    pb = b / sb if sb > 0 else b
    return 0.5 * float(np.abs(pa - pb).sum())


@dataclasses.dataclass
class Priced:
    cells: List[Tuple[int, int, int]]
    hit: np.ndarray
    io: np.ndarray
    distinct: np.ndarray           # (K,) pages with mass, per row


def price_window(dep: Deployment, window: Sequence[BatchProfile]) -> Priced:
    """Price the joint table on the sum of a window of batch profiles."""
    counts = sum(p.counts for p in window)
    n = sum(p.n_queries for p in window)
    dacs = sum(p.dacs * p.n_queries for p in window) / max(n, 1)
    cells = dep.table()
    rows = np.asarray([r for r, _, _ in cells])
    caps = np.asarray([c for _, _, c in cells])
    # solve each row once at all of its capacities
    hit = np.empty(len(cells), np.float64)
    for r in np.unique(rows):
        sel = rows == r
        hit[sel] = lru_hit_rates(np.repeat(counts[r][None, :], sel.sum(), 0),
                                 caps[sel])
    return Priced(cells, hit, (1.0 - hit) * dacs[rows],
                  (counts > 0).sum(axis=1).astype(np.float64))
