"""Structural page-reference estimators (paper §IV).

Given query *true positions* (ranks) and the index geometry (error bound
``eps``, items-per-page ``C_ipp``), these estimators derive the expected
page-reference histogram ``C_p`` — and from it the request distribution
``Pr_req(p)`` — WITHOUT replaying the workload.

TPU-native adaptation: the paper's per-query C++ loops become vectorized
gather (LUT), masked windowed adds, and one ``segment_sum`` scatter; the whole
estimator jits.

* Point queries  — Eq. 12/13 via the (d, s) lookup table (O(eps + C_ipp) entries).
* Range queries  — Eq. 14 via a difference array + prefix sum.
* Sorted (join)  — Theorem III.1 needs only (R, N); computed from interval
  unions with a cummax, no histogram required.
* RMI            — per-leaf mixture: grouped by distinct leaf error bound.

Shape-stable batches: a served batch's part sizes change every batch, and a
jitted estimator compiles once per input shape.  :func:`pad_to_bucket`
pads a part's lanes to a power of two (:func:`bucket_lanes`) and the
estimators that take ``n_valid`` give the padded lanes zero weight, so a
serving loop compiles once per bucket and not once per batch.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

__all__ = [
    "bucket_lanes",
    "pad_to_bucket",
    "point_lut",
    "point_page_refs",
    "point_page_refs_grid",
    "point_page_refs_mixed_eps",
    "point_page_refs_mixed_eps_grid",
    "mixed_eps_class_codes",
    "mixed_eps_class_eps",
    "range_page_refs",
    "range_page_refs_grid",
    "page_intervals",
    "sorted_workload_rn",
    "sorted_workload_stats",
    "sorted_window_stats",
    "point_access_prob_exact",
]


#: The smallest lane bucket: tiny parts share one compiled shape.
MIN_BUCKET = 256


def bucket_lanes(n: int) -> int:
    """Lanes a part of ``n`` references is padded to: the next power of
    two, at least :data:`MIN_BUCKET`."""
    return max(MIN_BUCKET, 1 << max(int(n) - 1, 0).bit_length())


def pad_to_bucket(*arrays) -> Tuple[Tuple[np.ndarray, ...], np.ndarray]:
    """Equal-length position arrays as int32, zero-padded to
    :func:`bucket_lanes` of their length, and the count of real lanes as an
    int32 scalar (a traced argument, so it never recompiles).  Counts
    ``profile.lanes`` and ``profile.pad_lanes``."""
    n = int(np.asarray(arrays[0]).shape[0])
    lanes = bucket_lanes(n)
    padded = []
    for a in arrays:
        out = np.zeros(lanes, np.int32)
        out[:n] = np.asarray(a).reshape(-1)
        padded.append(out)
    obs.count("profile.lanes", n)
    obs.count("profile.pad_lanes", lanes - n)
    return tuple(padded), np.int32(n)


def lut_radius(eps: int, c_ipp: int) -> int:
    """Max |page distance| d reachable from the true position's page."""
    return int(np.ceil(2 * eps / c_ipp))


@functools.partial(jax.jit, static_argnames=("eps", "c_ipp"))
def point_lut(eps: int, c_ipp: int) -> jnp.ndarray:
    """LUT[d + D, s] = Pr(page q+d accessed | in-page offset s) per Eq. 12.

    With the true position r = q*C_ipp + s and the error e ~ U{-eps..eps},
    page p = q + d is touched iff the window [r+e-eps, r+e+eps] intersects
    [p*C_ipp, (p+1)*C_ipp - 1].  Substituting p*C_ipp - r = d*C_ipp - s gives

        L(d,s) = max(-eps, d*C_ipp - s - eps)
        U(d,s) = min(+eps, d*C_ipp - s + C_ipp - 1 + eps)
        Pr     = max(0, U - L + 1) / (2*eps + 1)
    """
    d_radius = lut_radius(eps, c_ipp)
    d = jnp.arange(-d_radius, d_radius + 1)[:, None]      # (2D+1, 1)
    s = jnp.arange(c_ipp)[None, :]                        # (1, C_ipp)
    lo = jnp.maximum(-eps, d * c_ipp - s - eps)
    hi = jnp.minimum(eps, d * c_ipp - s + c_ipp - 1 + eps)
    width = jnp.maximum(0, hi - lo + 1)
    return width.astype(jnp.float32) / jnp.float32(2 * eps + 1)


def point_access_prob_exact(r: int, page: int, eps: int, c_ipp: int) -> float:
    """Brute-force enumeration of Eq. 12 (test oracle, O(eps))."""
    hits = 0
    for e in range(-eps, eps + 1):
        w_lo, w_hi = r + e - eps, r + e + eps
        p_lo, p_hi = page * c_ipp, (page + 1) * c_ipp - 1
        if w_lo <= p_hi and p_lo <= w_hi:
            hits += 1
    return hits / (2 * eps + 1)


@functools.partial(jax.jit, static_argnames=("eps", "c_ipp", "num_pages"))
def point_page_refs(
    positions: jnp.ndarray, eps: int, c_ipp: int, num_pages: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expected page-reference histogram for a point workload (Eq. 13).

    Args:
      positions: (Q,) int32 true ranks of the query keys.
      eps, c_ipp, num_pages: index geometry (static for jit).

    Returns:
      counts: (num_pages,) float32 expected reference counts ``C_p``.
      total:  scalar — total expected logical references R (window mass that
              falls on valid pages; boundary-clipped windows drop the
              out-of-range share, matching the clamped last-mile search).
    """
    lut = point_lut(eps, c_ipp)                            # (2D+1, C_ipp)
    d_radius = lut_radius(eps, c_ipp)
    positions = positions.astype(jnp.int32)
    q = positions // c_ipp
    s = positions % c_ipp
    contribs = lut[:, s].T                                 # (Q, 2D+1)
    targets = q[:, None] + jnp.arange(-d_radius, d_radius + 1)[None, :]
    valid = (targets >= 0) & (targets < num_pages)
    contribs = jnp.where(valid, contribs, 0.0)
    flat_t = jnp.where(valid, targets, 0).reshape(-1)
    counts = jax.ops.segment_sum(
        contribs.reshape(-1), flat_t, num_segments=num_pages
    )
    return counts, jnp.sum(contribs)


def _point_lut_traced(eps: jnp.ndarray, d_radius: int, c_ipp: int) -> jnp.ndarray:
    """Eq. 12 LUT with a *traced* eps and a static padded radius.

    Entries with |d| beyond the candidate's own radius get width 0 from the
    max(0, ·) clamp, so padding to the grid-wide max radius is exact — this is
    what lets a whole eps grid share one compiled kernel.
    """
    d = jnp.arange(-d_radius, d_radius + 1)[:, None]
    s = jnp.arange(c_ipp)[None, :]
    eps = eps.astype(jnp.int32)
    lo = jnp.maximum(-eps, d * c_ipp - s - eps)
    hi = jnp.minimum(eps, d * c_ipp - s + c_ipp - 1 + eps)
    width = jnp.maximum(0, hi - lo + 1)
    return width.astype(jnp.float32) / (2.0 * eps.astype(jnp.float32) + 1.0)


@functools.partial(jax.jit, static_argnames=("d_radius", "c_ipp", "num_pages"))
def point_page_refs_grid(
    positions: jnp.ndarray,
    eps_grid: jnp.ndarray,
    d_radius: int,
    c_ipp: int,
    num_pages: int,
    n_valid: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Eq. 13 histograms for a WHOLE eps grid in one compiled pass.

    Since every query at true position (q, s) contributes ``LUT[d, s]`` to
    page ``q + d``, the workload enters only through its (page, offset)
    occupancy histogram — computed ONCE and shared by every candidate.  Each
    candidate's page histogram is then a banded contraction

        counts_k[q + d] += sum_s pos_hist[q, s] * LUT_k[d, s]

    i.e. one (K*(2D+1), C_ipp) x (C_ipp, P) matmul plus 2D+1 shifted adds —
    no per-query scatter, no per-eps recompiles, work independent of |Q|
    beyond the single bincount.  This replaces K jit specializations of
    :func:`point_page_refs` in the legacy tuning loop.

    Args:
      positions: (Q,) true ranks, shared page-ref state for the grid.
      eps_grid:  (K,) int32 candidate error bounds.
      d_radius:  static padded radius — ``lut_radius(max(eps_grid), c_ipp)``.
      n_valid:   only the first ``n_valid`` positions count (the rest are
                 :func:`pad_to_bucket` lanes).

    Returns:
      counts: (K, num_pages) expected reference histograms (boundary-clipped,
              matching :func:`point_page_refs`).
      totals: (K,) total expected logical references per candidate.
    """
    k = eps_grid.shape[0]
    width = 2 * d_radius + 1
    weight = (jnp.arange(positions.shape[0]) < n_valid).astype(jnp.float32)
    pos_hist = jax.ops.segment_sum(
        weight,
        positions.astype(jnp.int32),
        num_segments=num_pages * c_ipp,
    ).reshape(num_pages, c_ipp)                            # shared state
    lut = _point_lut_traced(
        eps_grid.astype(jnp.int32)[:, None, None], d_radius, c_ipp
    )                                                      # (K, 2D+1, C_ipp)
    band = jnp.matmul(lut.reshape(k * width, c_ipp), pos_hist.T,
                      precision=jax.lax.Precision.HIGHEST).reshape(
        k, width, num_pages)                               # f32 on TPU too
    out = jnp.zeros((k, num_pages + 2 * d_radius), jnp.float32)
    for j in range(width):                                 # shifted adds
        out = out.at[:, j:j + num_pages].add(band[:, j, :])
    counts = out[:, d_radius:d_radius + num_pages]         # clip to valid pages
    return counts, jnp.sum(counts, axis=1)


@functools.partial(jax.jit, static_argnames=("c_ipp", "num_pages", "n"))
def range_page_refs_grid(
    lo_pos: jnp.ndarray,
    hi_pos: jnp.ndarray,
    eps_grid: jnp.ndarray,
    c_ipp: int,
    num_pages: int,
    n: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Eq. 14 histograms for an eps grid in one compiled pass (cf. point)."""
    lo_pos = lo_pos.astype(jnp.int32)
    hi_pos = hi_pos.astype(jnp.int32)

    def one(eps):
        eps = eps.astype(jnp.int32)
        start = jnp.maximum(0, lo_pos - 2 * eps) // c_ipp
        end = jnp.minimum(n - 1, hi_pos + 2 * eps) // c_ipp
        ones = jnp.ones_like(start, jnp.float32)
        diff = jax.ops.segment_sum(ones, start, num_segments=num_pages + 1)
        diff = diff - jax.ops.segment_sum(ones, end + 1, num_segments=num_pages + 1)
        counts = jnp.cumsum(diff)[:num_pages]
        return counts, jnp.sum((end - start + 1).astype(jnp.float32))

    return jax.lax.map(one, eps_grid.astype(jnp.int32))


def point_page_refs_mixed_eps(
    positions: np.ndarray,
    eps_per_query: np.ndarray,
    c_ipp: int,
    num_pages: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """RMI variant (§V-C): per-query leaf error bounds.

    Queries are grouped by distinct eps (leaf error bounds repeat heavily),
    and each group reuses the fixed-eps jitted estimator — so cost is
    O(#distinct_eps) compiles worst case, with LUTs of size O(eps + C_ipp).
    """
    positions = np.asarray(positions)
    eps_per_query = np.asarray(eps_per_query)
    counts = jnp.zeros((num_pages,), jnp.float32)
    total = jnp.zeros((), jnp.float32)
    for eps in np.unique(eps_per_query):
        sel = positions[eps_per_query == eps]
        c, t = point_page_refs(jnp.asarray(sel), int(max(eps, 1)), c_ipp, num_pages)
        counts = counts + c
        total = total + t
    return counts, total


#: Reusable host buffers for the mixed-eps grid kernel, keyed by
#: (dtype, tag) and grown geometrically.  The kernel is bandwidth-bound and
#: called in a warm tuning loop; fresh mmap-backed temporaries would pay
#: page-fault zeroing on every call.  Bounded by the largest grid profiled
#: (a few tens of MB); single-threaded use, like the session-level caches.
_SCRATCH: dict = {}

#: Max banded entries materialized at once (bounds each scratch buffer).
_SCRATCH_ENTRIES = 2_000_000


def _scratch(dtype, n: int, tag: str = "") -> np.ndarray:
    key = (np.dtype(dtype), tag)
    buf = _SCRATCH.get(key)
    if buf is None or buf.size < n:
        buf = np.empty(int(n * 1.25) + 16, dtype)
        _SCRATCH[key] = buf
    return buf[:n]


@functools.lru_cache(maxsize=256)
def _point_lut_np(eps: int, c_ipp: int) -> np.ndarray:
    """Eq. 12 LUT transposed to (C_ipp, 2D+1), float64, host-side.

    The mixed-eps grid kernel gathers whole LUT rows per reference, so the
    slot axis leads; float64 is deliberate — ``np.bincount`` casts weights
    to float64 internally, so a narrower gather would just add a copy.
    """
    d_radius = lut_radius(eps, c_ipp)
    s = np.arange(c_ipp)[:, None]
    d = np.arange(-d_radius, d_radius + 1)[None, :] * c_ipp
    lo = np.maximum(-eps, d - s - eps)
    hi = np.minimum(eps, d - s + c_ipp - 1 + eps)
    return np.maximum(0, hi - lo + 1) / float(2 * eps + 1)


def mixed_eps_class_codes(
    flat_eps: np.ndarray,
) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """Eps-class codes shared by the host and device mixed-eps kernels.

    Class codes without a sort over K*Q elements: pow2-quantized bounds
    (the adapters' contract) map to their exponent — popcount(e - 1) —
    while arbitrary bounds (third-party callers) fall back to unique-rank
    codes.  Returns ``(codes, classes)``: ``codes[i]`` is the class code of
    ``flat_eps[i]``; ``classes`` is ``None`` for pow2 inputs (decode with
    :func:`mixed_eps_class_eps`) or the sorted unique eps values otherwise.
    Both kernels MUST group through this one helper so their per-class LUT
    layouts stay aligned (pinned by the host-vs-device oracle suite).
    """
    flat_eps = np.asarray(flat_eps, np.int64)
    if np.bitwise_and(flat_eps, flat_eps - 1).any():
        classes, codes = np.unique(flat_eps, return_inverse=True)
        if len(classes) <= 256:             # byte compares in the class loop
            codes = codes.astype(np.uint8)
        return codes, classes
    if hasattr(np, "bitwise_count"):
        return np.bitwise_count(flat_eps - 1), None
    codes = np.rint(np.log2(flat_eps.astype(np.float64))).astype(np.uint8)
    return codes, None


def mixed_eps_class_eps(code: int, classes: Optional[np.ndarray]) -> int:
    """Decode a :func:`mixed_eps_class_codes` code back to its eps value."""
    return int(classes[code]) if classes is not None else 1 << int(code)


def point_page_refs_mixed_eps_grid(
    positions: np.ndarray,
    eps_rows: np.ndarray,
    c_ipp: int,
    num_pages: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """Mixed-eps histograms for a WHOLE candidate grid in one grouped pass.

    The batched counterpart of :func:`point_page_refs_mixed_eps` for RMI
    branch grids (§V-C): ``eps_rows[k, i]`` is candidate k's error bound for
    the i-th query (its routed leaf's quantized bound), over the SHARED
    ``positions``.  References are grouped by quantized eps ACROSS the whole
    grid with ONE stable argsort — leaf bounds are pow2-quantized, so the
    union has ~log2(max_eps) classes — and each class does one banded
    LUT-row gather plus one ``np.bincount`` into a padded (K, P + 2D)
    histogram (out-of-range window mass lands in the pad and is sliced off,
    reproducing :func:`point_page_refs`'s boundary clipping without a mask).

    This kernel is deliberately host-side: its cost is one weighted scatter
    of ~R_total banded contributions, and on the CPU backends that run the
    tuning loops XLA lowers ``segment_sum`` to a serial scatter (~10x slower
    per entry than ``np.bincount``), which is exactly the bottleneck of the
    per-branch path this replaces — K x #distinct-eps jitted scatters plus
    as many dispatch round trips.  The downstream hit-rate solve stays one
    vmapped jit; the histograms it consumes are device-uploaded once.

    Returns (counts (K, num_pages) float32, totals (K,) float64).
    """
    positions = np.asarray(positions, np.int64)
    eps_rows = np.maximum(np.asarray(eps_rows, np.int64), 1)
    k, q_n = eps_rows.shape
    if positions.shape[0] != q_n:
        raise ValueError(f"eps_rows has {q_n} columns for "
                         f"{positions.shape[0]} positions")
    page = positions // c_ipp
    slot = positions - page * c_ipp
    max_radius = lut_radius(int(eps_rows.max()), c_ipp)
    pad = num_pages + 2 * max_radius
    counts = np.zeros(k * pad, np.float64)

    codes, classes = mixed_eps_class_codes(eps_rows.ravel())
    # Shared flat arrays: row*pad + page in one precomputed vector, so each
    # class needs exactly two gathers before its banded bincount.  All big
    # temporaries live in the module scratch pool — the kernel is memory-
    # bound, and re-faulting ~25 MB of fresh mmap pages per warm call would
    # cost as much as the arithmetic it feeds.
    prebase = _scratch(np.int64, k * q_n).reshape(k, q_n)
    np.add(np.arange(k, dtype=np.int64)[:, None] * pad, page[None, :],
           out=prebase)
    prebase = prebase.reshape(-1)
    slot_tiled = _scratch(np.int32, k * q_n).reshape(k, q_n)
    np.copyto(slot_tiled, slot.astype(np.int32)[None, :])
    slot_tiled = slot_tiled.reshape(-1)
    for code in np.flatnonzero(np.bincount(codes)):
        eps = mixed_eps_class_eps(code, classes)
        class_idx = np.flatnonzero(codes == code)
        radius = lut_radius(eps, c_ipp)
        width = 2 * radius + 1
        lut = _point_lut_np(eps, c_ipp)
        offs = np.arange(width)[None, :]
        # Wide-window classes (tiny branch factors) chunk so the scratch
        # pool stays bounded (~30 MB) whatever the grid.
        chunk = max(1, _SCRATCH_ENTRIES // width)
        for a in range(0, class_idx.shape[0], chunk):
            idx = class_idx[a:a + chunk]
            t = idx.shape[0]
            w = _scratch(np.float64, t * width, "w").reshape(t, width)
            np.take(lut, slot_tiled[idx], axis=0, out=w)   # (T, 2D+1) rows
            base = _scratch(np.int64, t, "base")
            np.take(prebase, idx, out=base)
            base += max_radius - radius
            flat = _scratch(np.int64, t * width, "flat").reshape(t, width)
            np.add(base[:, None], offs, out=flat)
            counts += np.bincount(flat.reshape(-1), weights=w.reshape(-1),
                                  minlength=k * pad)
    valid = counts.reshape(k, pad)[:, max_radius:max_radius + num_pages]
    return valid.astype(np.float32), valid.sum(axis=1)


@functools.partial(jax.jit, static_argnames=("eps", "c_ipp", "num_pages", "n"))
def range_page_refs(
    lo_pos: jnp.ndarray,
    hi_pos: jnp.ndarray,
    eps: int,
    c_ipp: int,
    num_pages: int,
    n: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Range-workload histogram via Eq. 14 + difference array.

    S(Q) = floor(max(0, r(lo) - 2eps) / C_ipp)
    E(Q) = floor(min(n-1, r(hi) + 2eps) / C_ipp)

    Returns (counts, total_refs R); E[DAC] = R / |Q|.
    """
    start = jnp.maximum(0, lo_pos.astype(jnp.int32) - 2 * eps) // c_ipp
    end = jnp.minimum(n - 1, hi_pos.astype(jnp.int32) + 2 * eps) // c_ipp
    ones = jnp.ones_like(start, jnp.float32)
    diff = jax.ops.segment_sum(ones, start, num_segments=num_pages + 1)
    diff = diff - jax.ops.segment_sum(ones, end + 1, num_segments=num_pages + 1)
    counts = jnp.cumsum(diff)[:num_pages]
    total = jnp.sum((end - start + 1).astype(jnp.float32))
    return counts, total


@functools.partial(jax.jit, static_argnames=("c_ipp", "num_pages"))
def page_intervals(
    window_lo: jnp.ndarray, window_hi: jnp.ndarray, c_ipp: int, num_pages: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Map position windows to inclusive page intervals (PAGEINTERVALS in Alg. 2)."""
    lo = jnp.clip(window_lo, 0, None) // c_ipp
    hi = jnp.clip(window_hi, None, num_pages * c_ipp - 1) // c_ipp
    return lo.astype(jnp.int32), jnp.clip(hi, lo, num_pages - 1).astype(jnp.int32)


@jax.jit
def sorted_workload_rn(
    page_lo: jnp.ndarray, page_hi: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(R, N) for a sorted probe stream (Theorem III.1 inputs).

    R = sum of window widths; N = |union of intervals|.  For intervals sorted
    by ``page_lo`` the union size is a running-cummax sweep — O(|Q|), no
    histogram materialization.
    """
    widths = (page_hi - page_lo + 1).astype(jnp.float32)
    r_total = jnp.sum(widths)
    prev_hi = jnp.concatenate(
        [jnp.array([-1], page_hi.dtype), jax.lax.cummax(page_hi)[:-1]]
    )
    new_lo = jnp.maximum(page_lo, prev_hi + 1)
    n_distinct = jnp.sum(jnp.maximum(0, page_hi - new_lo + 1).astype(jnp.float32))
    return r_total, n_distinct


def _interval_stats(lo, hi, valid, num_pages: int):
    """(R, N, coverage, pinned_retouches, widest) of the page intervals
    ``valid`` marks; the other lanes add nothing."""
    w = valid.astype(jnp.float32)
    diff = jax.ops.segment_sum(w, lo, num_segments=num_pages + 1)
    diff = diff - jax.ops.segment_sum(w, hi + 1, num_segments=num_pages + 1)
    coverage = jnp.cumsum(diff)[:num_pages]
    widths = jnp.where(valid, hi - lo + 1, 0)
    r_total = jnp.sum(widths.astype(jnp.float32))
    n_distinct = jnp.sum(coverage > 0).astype(jnp.float32)
    pinned = jnp.sum(((lo[1:] == hi[:-1]) & valid[1:]).astype(jnp.float32))
    return r_total, n_distinct, coverage, pinned, jnp.max(widths, initial=0)


@functools.partial(jax.jit, static_argnames=("c_ipp", "num_pages"))
def sorted_window_stats(
    window_lo: jnp.ndarray, window_hi: jnp.ndarray, n_valid: jnp.ndarray,
    c_ipp: int, num_pages: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`page_intervals` then :func:`sorted_workload_stats` of the
    first ``n_valid`` windows of bucket-padded ones, in one compiled pass.

    Returns ``(stats, coverage)``: ``stats`` is float32 (R, N,
    pinned_retouches, widest window in pages), one host read; padded lanes
    add nothing to any of them.
    """
    lo, hi = page_intervals(window_lo, window_hi, c_ipp, num_pages)
    r_total, n_distinct, coverage, pinned, widest = _interval_stats(
        lo, hi, jnp.arange(lo.shape[0]) < n_valid, num_pages)
    stats = jnp.stack([r_total, n_distinct, pinned,
                       widest.astype(jnp.float32)])
    return stats, coverage


def sorted_workload_stats(
    page_lo: jnp.ndarray, page_hi: jnp.ndarray, num_pages: int
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(R, N, coverage, pinned_retouches) for a sorted probe stream.

    Deliberately NOT jitted: the join planner calls it with
    outer-relation-sized arrays whose shapes vary call to call, and a
    per-shape retrace would cost more than the handful of eager ops here
    (one scatter, one scan, two reductions).  Callers that profile every
    batch use :func:`sorted_window_stats` on bucket-padded windows.

    Extends :func:`sorted_workload_rn` with the two statistics the
    frequency-aware sorted-scan model (``cache_models.sorted_scan_*``)
    needs beyond Theorem III.1's (R, N):

    * ``coverage`` — the window-coverage histogram ``coverage[p] = number of
      probe windows covering page p`` (difference array + prefix sum, same
      shape as the Eq. 13/14 histograms, so it can also join a mixed
      workload's request distribution);
    * ``pinned_retouches`` — references that survive eviction pressure under
      ANY policy state: a reference to the page the immediately preceding
      reference touched cannot be separated from it by an insertion, so no
      eviction can occur in between.  For a sorted stream the worst-case
      residency recursion (every other re-reference assumed to re-insert)
      collapses — the proven-resident set between insertions is always the
      single most recent page — so its least fixed point is exactly the
      window-junction count ``sum(lo[i+1] == hi[i])``.  This subsumes the
      width-1 repeat ("solo") count and is the pressure correction used by
      ``cache_models.sorted_scan_misses``.
    """
    lo = jnp.asarray(page_lo, jnp.int32)
    hi = jnp.asarray(page_hi, jnp.int32)
    r_total, n_distinct, coverage, pinned, _ = _interval_stats(
        lo, hi, jnp.ones(lo.shape[0], bool), num_pages)
    return r_total, n_distinct, coverage, pinned
