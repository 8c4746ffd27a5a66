"""Plain reference of the write path's semantics, in float64.

Straight NumPy from the paper's definitions; it imports nothing of the
program under test.  From the key set, the configuration and the traffic
pool it re-derives what a write-path batch decides:

* the index footprint: a PGM-index (Ferragina & Vinciguerra, VLDB 2020),
  greedy eps-PLA levels of 16-byte segments, recursive until one segment
  is left (a copy of the build, so the buffer's capacity is the same);
* the read profile: Eq. 12/13 histograms at the uniform bound eps, and
  E[DAC] = 1 + 2 eps / C_ipp (Lemma III.2, all-at-once fetch);
* the delta: staged updates steal ceil(entries * entry_bytes / page_bytes)
  buffer pages until merged;
* the merge burst: the staged ranks' pages in key order, runs of pages at
  most one page apart coalesced into one sorted window each;
* pricing under LFU: the converged top-C mass of the read histogram
  (Eq. 9), the compulsory closed form where the buffer holds every page;
  the burst by the sorted-stream form (thrash below the widest window,
  compulsory at or above N distinct pages, else the top-C coverage and
  window-junction bounds clamped to [N, R]), each miss paid twice (read
  and written back);
* the merge rule: merge when the delta is full, or when deferring's extra
  read misses over the horizon outweigh the burst.

``lut_round`` runs the same pipeline with the Eq. 12 weights rounded to a
lower precision: the control.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from reference import capacity, locate, num_pages, point_histogram

SEGMENT_BYTES = 16          # first key, slope, intercept of a PLA segment


# ---------------------------------------------------------------------------
# The index footprint
# ---------------------------------------------------------------------------

def _segment_end(x: np.ndarray, j: int, eps: float) -> int:
    """End (exclusive) of the segment anchored at ``x[j]``: the first key
    past which no line through the anchor keeps every rank within eps."""
    n = x.shape[0]
    lo_run, hi_run = -np.inf, np.inf
    i, window = j + 1, 64
    while i < n:
        stop = min(n, i + window)
        dx = (x[i:stop] - x[j]).astype(np.float64)
        dy = np.arange(i - j, stop - j, dtype=np.float64)
        lo_s = np.maximum(np.maximum.accumulate((dy - eps) / dx), lo_run)
        hi_s = np.minimum(np.minimum.accumulate((dy + eps) / dx), hi_run)
        bad = lo_s > hi_s
        if bad.any():
            return i + int(np.argmax(bad))
        lo_run, hi_run = float(lo_s[-1]), float(hi_s[-1])
        i, window = stop, min(window * 2, 1 << 20)
    return n


def pla_first_keys(keys: np.ndarray, eps: int) -> np.ndarray:
    """The anchor key of each greedy eps-PLA segment over ``keys``."""
    firsts, j, n = [], 0, keys.shape[0]
    while j < n:
        firsts.append(j)
        j = _segment_end(keys, j, float(eps))
    return keys[np.asarray(firsts, np.int64)]


def pgm_size_bytes(keys: np.ndarray, eps: int) -> int:
    """Bytes of every PGM level: segments over the keys, then segments
    over each level's anchors, until one segment (or no progress)."""
    level = pla_first_keys(np.asarray(keys), eps)
    total = level.shape[0]
    while level.shape[0] > 1:
        up = pla_first_keys(level, eps)
        total += up.shape[0]
        if up.shape[0] >= level.shape[0]:
            break
        level = up
    return SEGMENT_BYTES * total


# ---------------------------------------------------------------------------
# Profiles
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ReadProfile:
    positions: np.ndarray      # (Q,) ranks
    counts: np.ndarray         # (P,) expected references per page
    total: float               # R: the histogram's mass
    dac: float                 # E[DAC] per read


@dataclasses.dataclass
class Burst:
    lo: np.ndarray             # (W,) first rank of each sorted window
    hi: np.ndarray             # (W,) last rank
    coverage: np.ndarray       # (P,) windows covering each page
    refs: float                # R: pages referenced, window by window
    distinct: float            # N: pages covered
    pinned: float              # window junctions: lo page == previous hi page
    widest: float              # pages of the widest window

    @property
    def stats(self) -> np.ndarray:
        return np.asarray([self.refs, self.distinct, self.pinned,
                           self.widest])


@dataclasses.dataclass
class Priced:
    io_defer: float            # I/O per read at the shrunken capacity
    io_merged: float           # I/O per read at the whole buffer
    merge_io: float            # the burst's physical I/O


class WriteDeployment:
    """Key set, geometry, budget, index bound and delta of one config."""

    def __init__(self, keys: np.ndarray, *, eps: int, c_ipp: int,
                 page_bytes: int, budget_bytes: float, write: dict,
                 safety: float = 1.0):
        self.keys = keys
        self.n = int(keys.shape[0])
        self.eps = int(eps)
        self.c_ipp = int(c_ipp)
        self.page_bytes = int(page_bytes)
        self.pages = num_pages(self.n, c_ipp)
        self.index_bytes = pgm_size_bytes(keys, eps)
        self.cap_empty = capacity(budget_bytes, self.index_bytes, page_bytes)
        self.entry_bytes = float(write["delta_entry_bytes"])
        self.delta_capacity = int(write["delta_capacity_entries"])
        self.horizon_batches = float(write["horizon_batches"])
        self.merge_write_factor = float(write["merge_write_factor"])
        self.safety = float(safety)
        self.dac = 1.0 + 2.0 * self.eps / self.c_ipp

    def cap_now(self, delta_entries: int) -> int:
        stolen = math.ceil(delta_entries * self.entry_bytes
                           / self.page_bytes)
        return max(self.cap_empty - stolen, 0)

    def read_profile(self, query_keys: np.ndarray,
                     lut_round: Optional[Callable] = None) -> ReadProfile:
        pos = locate(self.keys, query_keys)
        counts = point_histogram(pos, np.full(pos.shape[0], self.eps),
                                 self.c_ipp, self.pages, lut_round)
        return ReadProfile(pos, counts, float(counts.sum()), self.dac)

    def burst(self, update_keys: np.ndarray) -> Burst:
        pages = np.unique(locate(self.keys, update_keys) // self.c_ipp)
        split = np.flatnonzero(np.diff(pages) > 1) + 1
        first = pages[np.r_[0, split]]
        last = pages[np.r_[split - 1, pages.shape[0] - 1]]
        lo = np.minimum(first * self.c_ipp, self.n - 1)
        hi = np.minimum(last * self.c_ipp + self.c_ipp - 1, self.n - 1)
        plo, phi = lo // self.c_ipp, hi // self.c_ipp
        coverage = np.zeros(self.pages, np.float64)
        for a, b in zip(plo, phi):
            coverage[a:b + 1] += 1.0
        widths = phi - plo + 1
        return Burst(lo, hi, coverage, float(widths.sum()),
                     float(np.count_nonzero(coverage)),
                     float(np.sum(plo[1:] == phi[:-1])), float(widths.max()))

    # ------------------------------------------------------------- pricing
    def price(self, window: Sequence[ReadProfile], delta_entries: int,
              burst: Optional[Burst]) -> Priced:
        counts = sum(p.counts for p in window)
        reads = sum(p.positions.shape[0] for p in window)
        dac = sum(p.dac * p.positions.shape[0] for p in window) / reads
        io_defer = (1.0 - lfu_hit(counts, self.cap_now(delta_entries))) * dac
        io_merged = (1.0 - lfu_hit(counts, self.cap_empty)) * dac
        misses = (0.0 if burst is None
                  else sorted_lfu_misses(burst, self.cap_empty))
        return Priced(io_defer, io_merged, self.merge_write_factor * misses)

    def merges(self, priced: Priced, n_reads: int, delta_entries: int,
               margin: float = 0.0) -> Optional[bool]:
        """The merge rule; None where benefit and cost lie within
        ``margin`` of each other."""
        if delta_entries == 0:
            return False
        if delta_entries >= self.delta_capacity:
            return True
        benefit = (max(priced.io_defer - priced.io_merged, 0.0)
                   * self.horizon_batches * n_reads)
        cost = priced.merge_io * self.safety
        if abs(benefit - cost) <= margin * max(abs(benefit), abs(cost)):
            return None
        return benefit > cost


def lfu_hit(counts: np.ndarray, cap: int) -> float:
    """Converged LFU (Eq. 9): the mass of the ``cap`` most referenced pages;
    (R - N) / R where the buffer holds all N referenced pages."""
    total = float(counts.sum())
    if total <= 0 or cap < 1:
        return 0.0
    distinct = int(np.count_nonzero(counts))
    if cap >= distinct:
        return (total - distinct) / total
    top = np.sort(counts)[::-1][:cap]
    return float(top.sum()) / total


def sorted_lfu_misses(burst: Burst, cap: int) -> float:
    """Misses of the sorted burst under LFU at ``cap`` pages."""
    r, n = burst.refs, burst.distinct
    if r <= 0:
        return 0.0
    if cap < burst.widest:                  # no window fits: thrash
        return min(max(r - burst.pinned, n), r)
    if cap >= n:
        return n
    top = float(np.sort(burst.coverage)[::-1][:cap].sum())
    return min(max(min(r - top, r - burst.pinned), n), r)


def window_of(seq: int, window_chunks: int) -> List[int]:
    """The batches a decision at batch ``seq`` prices: the last
    ``window_chunks`` batches, every one of which holds reads."""
    return list(range(max(0, seq - window_chunks + 1), seq + 1))


def delta_since(seq: int, merged: Sequence[bool]) -> Tuple[int, int]:
    """(first, last) batch whose updates sit in the delta when batch
    ``seq`` decides: those after the last merge before it."""
    first = 0
    for s in range(seq - 1, -1, -1):
        if merged[s]:
            first = s + 1
            break
    return first, seq
