"""Policy-specific buffer hit-rate models (paper §III-B, §III-C).

All estimators operate on a page-request probability vector ``probs``
(``Pr_req(i)`` in the paper) and a buffer capacity ``C`` in pages.  They are
written as pure ``jnp`` programs so the whole CAM pipeline jits; the
fixed-point solves use a fixed-iteration bisection (monotone objectives) that
lowers to a tight ``fori_loop``.

Models implemented
------------------
* ``hit_rate_lru``  — Che's approximation (Eq. 7/8).
* ``hit_rate_fifo`` — Fricker's fixed point (Eq. 4/5/6); equals RANDOM under IRM.
* ``hit_rate_lfu``  — converged top-C mass (Eq. 9).
* ``hit_rate_compulsory`` — ``(R - N) / R`` for the large-capacity case and for
  sorted workloads under recency eviction (Theorem III.1).
* ``sorted_scan_misses`` / ``sorted_scan_hit_rate`` / the vmapped
  ``sorted_scan_hit_rate_grid`` — the policy-aware sorted-scan family: the
  compulsory closed form where Theorem III.1's premises hold (recency
  eviction, capacity above one probe window), a frequency-aware closed form
  from the window-coverage histogram for LFU-like policies, and the thrash
  regime below the capacity premise.  This is the ONE sorted-stream miss
  model shared by ``CostSession._finish`` and the join planner.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = [
    "solve_che_time",
    "hit_rate_lru",
    "solve_fifo_tau",
    "hit_rate_fifo",
    "hit_rate_lfu",
    "hit_rate_compulsory",
    "hit_rate",
    "hit_rate_grid",
    "writeback_fraction",
    "sorted_scan_misses",
    "sorted_scan_hit_rate",
    "sorted_scan_hit_rate_grid",
    "sorted_scan_miss_curve",
    "hit_rate_curve",
    "POLICIES",
    "RECENCY_POLICIES",
]

POLICIES = ("lru", "fifo", "lfu")

#: Policies whose eviction order tracks recency.  For these Theorem III.1's
#: proof step — "no page of the current probe window is evicted before the
#: probe finishes" — holds whenever the buffer fits one window, so the
#: compulsory closed form is exact for sorted streams.  Frequency-based
#: policies (LFU) violate it: stale high-frequency pages pin buffer slots and
#: the advancing scan frontier is evicted (with its frequency reset), so they
#: take the frequency-aware form below instead.
RECENCY_POLICIES = ("lru", "fifo")

_BISECT_ITERS = 64  # float32 bisection converges long before this

#: Largest capacity the exact compare path represents (int32).  Saturating
#: here is lossless for regime dispatch: every distinct-page count is far
#: below it, so any saturated capacity is already in the compulsory regime.
_CAP_MAX = 2**31 - 129


def _exact_caps(values) -> jnp.ndarray:
    """Integer-exact page counts for regime compares.

    float32 represents integers exactly only up to 2^24 (a 64 GiB pool at
    4 KiB pages), so ``capacity >= n_distinct``-style compares on float32
    capacities can flip on the rounded value.  Integer inputs pass through
    as int32 (exact to 2^31 pages); float inputs floor — for an integral
    threshold ``floor(c) >= n`` iff ``c >= n`` and ``floor(c) < n`` iff
    ``c < n`` — so float callers keep their semantics while integer callers
    gain exact compares.  Saturates at ``_CAP_MAX`` to keep the float→int
    conversion defined.
    """
    arr = jnp.asarray(values)
    if jnp.issubdtype(arr.dtype, jnp.integer):
        return jnp.minimum(arr.astype(jnp.int32), jnp.int32(_CAP_MAX))
    return jnp.clip(jnp.floor(arr), -1.0, float(_CAP_MAX)).astype(jnp.int32)


def _bisect(f, lo: jnp.ndarray, hi: jnp.ndarray, iters: int = _BISECT_ITERS):
    """Fixed-iteration bisection for a monotone-increasing scalar objective."""

    def body(_, state):
        lo, hi = state
        mid = 0.5 * (lo + hi)
        val = f(mid)
        lo = jnp.where(val < 0.0, mid, lo)
        hi = jnp.where(val < 0.0, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return 0.5 * (lo + hi)


# ---------------------------------------------------------------------------
# LRU — Che's approximation
# ---------------------------------------------------------------------------

def solve_che_time(probs: jnp.ndarray, capacity) -> jnp.ndarray:
    """Characteristic time T_C from the consistency condition (Eq. 8):

        C = sum_i (1 - exp(-p_i * T_C))

    The RHS is monotone increasing in ``T_C`` and saturates at ``N`` (the
    number of pages with nonzero probability), so a solution exists whenever
    ``C < N``; callers handle ``C >= N`` via :func:`hit_rate_compulsory`.
    """
    probs = jnp.asarray(probs, jnp.float64 if probs.dtype == jnp.float64 else jnp.float32)
    capacity = jnp.asarray(capacity, probs.dtype)

    def objective(t):
        return jnp.sum(-jnp.expm1(-probs * t)) - capacity

    # Upper bracket: occupancy of every page is >= 1 - exp(-p_min*T); the
    # solution is below C / p_min-ish.  Grow a safe bracket from the mean.
    pmin = jnp.maximum(jnp.min(jnp.where(probs > 0, probs, jnp.inf)), 1e-30)
    hi = jnp.maximum(4.0 * capacity / pmin, jnp.asarray(1.0, probs.dtype))
    lo = jnp.zeros_like(hi)
    return _bisect(objective, lo, hi)


def hit_rate_lru(probs: jnp.ndarray, capacity) -> jnp.ndarray:
    """Che's approximation for LRU (Eq. 7)."""
    t_c = solve_che_time(probs, capacity)
    return jnp.sum(probs * -jnp.expm1(-probs * t_c))


# ---------------------------------------------------------------------------
# FIFO — Fricker's fixed point (== RANDOM under IRM)
# ---------------------------------------------------------------------------

def solve_fifo_tau(probs: jnp.ndarray, capacity) -> jnp.ndarray:
    """Characteristic time tau_C from the consistency condition (Eq. 5):

        C = sum_i p_i * tau / (1 - p_i + p_i * tau)

    Monotone increasing in ``tau`` with limit ``N``; bisection as for Che.
    """
    probs = jnp.asarray(probs, jnp.float64 if probs.dtype == jnp.float64 else jnp.float32)
    capacity = jnp.asarray(capacity, probs.dtype)

    def objective(tau):
        occ = probs * tau / (1.0 - probs + probs * tau)
        return jnp.sum(occ) - capacity

    pmin = jnp.maximum(jnp.min(jnp.where(probs > 0, probs, jnp.inf)), 1e-30)
    hi = jnp.maximum(4.0 * capacity / pmin, jnp.asarray(1.0, probs.dtype))
    lo = jnp.zeros_like(hi)
    return _bisect(objective, lo, hi)


def hit_rate_fifo(probs: jnp.ndarray, capacity) -> jnp.ndarray:
    """Fricker's FIFO/RANDOM stationary hit rate (Eq. 4 + Eq. 6)."""
    tau = solve_fifo_tau(probs, capacity)
    h_i = probs * tau / (1.0 - probs + probs * tau)
    return jnp.sum(probs * h_i)


# ---------------------------------------------------------------------------
# LFU — converged steady state
# ---------------------------------------------------------------------------

def hit_rate_lfu(probs: jnp.ndarray, capacity) -> jnp.ndarray:
    """Converged LFU keeps the C most popular pages (Eq. 9).

    ``capacity`` may be a traced scalar; we sort once and take a masked
    prefix sum so the function stays jittable.
    """
    probs = jnp.asarray(probs)
    order = jnp.argsort(-probs)
    sorted_p = probs[order]
    ranks = jnp.arange(sorted_p.shape[0])
    # clip before the int cast so huge float capacities stay well-defined
    cap = jnp.clip(jnp.asarray(capacity), 0, sorted_p.shape[0])
    mask = ranks < cap.astype(ranks.dtype)
    return jnp.sum(jnp.where(mask, sorted_p, 0.0))


# ---------------------------------------------------------------------------
# Dirty-page writeback — the second physical-I/O stream of a mutating mix
# ---------------------------------------------------------------------------

def _writeback_terms(policy: str, probs: jnp.ndarray, wprobs: jnp.ndarray,
                     capacity) -> jnp.ndarray:
    """Expected writebacks per reference for ONE (histogram, capacity) cell.

    A write dirties its page in the pool; the dirty bit is flushed (one
    physical write I/O) when the page is EVICTED — so the writeback stream
    is the dirty-eviction rate, computable from the SAME characteristic-time
    fixed point the hit rate already solves (no second bisection):

    * page ``i``'s eviction rate equals its insertion (miss) rate,
      ``q_i * (1 - o_i)`` per reference, with ``q_i`` the combined
      read+write reference probability and ``o_i`` the policy occupancy
      (Che Eq. 7 for LRU, Fricker Eq. 4 for FIFO);
    * the evicted copy is dirty iff its residency started with a write
      (prob ``w_i / q_i``) or a write arrived during the residency window
      ``T`` (prob ``1 - exp(-w_i * T)`` for a read-born copy), giving

          wb = sum_i (1 - o_i) * (w_i + r_i * (1 - exp(-w_i * T))),
          r_i = q_i - w_i.

    Limits sanity-check the form: ``C -> 0`` gives ``wb -> sum_i w_i`` (every
    write flushes straight through), a pinned hot page (``o_i -> 1``) absorbs
    its writes entirely.  Converged LFU never evicts its top-C pages, so its
    writeback is exactly the write mass landing OUTSIDE the retained set —
    the write-mass prefix sum under the combined-popularity order (ties
    break identically to Eq. 9's ``argsort``, which keeps host and device
    executors bit-aligned).
    """
    probs = jnp.asarray(probs)
    wprobs = jnp.asarray(wprobs)
    if policy == "lfu":
        order = jnp.argsort(-probs)
        w_sorted = wprobs[order]
        prefix = jnp.cumsum(w_sorted)
        cap = jnp.clip(jnp.asarray(capacity), 0,
                       probs.shape[0]).astype(jnp.int32)
        kept = jnp.where(cap > 0, prefix[jnp.maximum(cap - 1, 0)], 0.0)
        return jnp.sum(wprobs) - kept
    if policy == "lru":
        t = solve_che_time(probs, capacity)
        occ = -jnp.expm1(-probs * t)
    elif policy == "fifo":
        t = solve_fifo_tau(probs, capacity)
        occ = probs * t / (1.0 - probs + probs * t)
    else:
        raise ValueError(f"unknown policy {policy!r}; "
                         f"expected one of {POLICIES}")
    r = jnp.maximum(probs - wprobs, 0.0)
    dirty = wprobs + r * -jnp.expm1(-wprobs * t)
    return jnp.sum((1.0 - occ) * dirty)


def writeback_fraction(policy: str, probs: jnp.ndarray, wprobs: jnp.ndarray,
                       capacity, n_distinct=None) -> jnp.ndarray:
    """Regime-dispatched :func:`_writeback_terms` for one candidate.

    ``probs`` is the COMBINED read+write reference-probability vector,
    ``wprobs`` its write component.  Above ``N`` distinct pages nothing is
    ever evicted, so steady-state writeback is zero (dirty pages stay
    resident — the amortized semantics the replay oracle mirrors); below one
    page every write flushes through.  Subtracting the result from the hit
    rate prices the mix: ``io = (1 - (h - wb)) * E[DAC]`` counts fetches AND
    flushes per reference.
    """
    probs = jnp.asarray(probs, jnp.float32)
    wprobs = jnp.asarray(wprobs, jnp.float32)
    nd = (jnp.sum(probs > 0) if n_distinct is None
          else jnp.asarray(n_distinct))
    cap_i = _exact_caps(jnp.asarray(capacity))
    wb = _writeback_terms(policy, probs, wprobs,
                          jnp.maximum(jnp.asarray(capacity, jnp.float32),
                                      1.0))
    wb = jnp.where(cap_i >= _exact_caps(nd), 0.0, wb)
    return jnp.where(cap_i < 1, jnp.sum(wprobs), wb)


# ---------------------------------------------------------------------------
# Compulsory-miss closed form (C >= N, and sorted workloads via Thm III.1)
# ---------------------------------------------------------------------------

def hit_rate_compulsory(total_requests, distinct_pages) -> jnp.ndarray:
    """h = (R - N) / R — each distinct page misses exactly once."""
    r = jnp.asarray(total_requests, jnp.float32)
    n = jnp.asarray(distinct_pages, jnp.float32)
    return jnp.where(r > 0, (r - n) / jnp.maximum(r, 1.0), 0.0)


# ---------------------------------------------------------------------------
# Sorted-scan model family (Theorem III.1 + policy-aware extensions)
# ---------------------------------------------------------------------------

@jax.jit
def _sorted_scan_misses_freq(coverage: jnp.ndarray, capacity,
                             pinned_retouches) -> jnp.ndarray:
    """Frequency-aware sorted-scan miss count from the coverage histogram.

    A frequency-based cache breaks the recency premise of Theorem III.1 in a
    specific way: eviction resets a page's frequency, so the advancing scan
    frontier keeps being evicted by stale pages whose counts were accumulated
    earlier, and re-misses on re-entry.  Two hit sources survive this
    pathology, and each yields a closed-form hit lower bound:

    * steady-state retention — the converged cache keeps the ``C`` pages
      with the highest coverage (Eq. 9 applied to the coverage histogram),
      whose references hit once resident: ``miss <= R - topC_mass``;
    * pressure-pinned re-touches — the least fixed point of the worst-case
      eviction-pressure recursion (every non-surviving re-reference assumed
      to re-insert and evict).  For sorted streams this collapses to the
      window-junction count ``pinned = sum(lo[i+1] == hi[i])`` (see
      ``page_ref.sorted_workload_stats``): those references hit under ANY
      eviction state, so ``miss <= R - pinned``.

    The model takes the tighter bound and clamps to ``[N, R]`` (compulsory
    floor, thrash ceiling).  Replay-validated against
    ``repro.core.replay.LFUBuffer`` across PGM / RMI / RadixSpline streams
    at tuning-relevant capacities (q-error < 2), and — via the pinned
    correction — on strongly recency-like narrow-window streams at small
    capacities, where the junction bound is tight (q-error ~ 1.0-1.1 on
    width-2 sliding windows and dense jittered width-1/2 streams that the
    width-1 "solo" statistic under-counted by ~2x).
    """
    cov = jnp.asarray(coverage, jnp.float32)
    prefix = jnp.cumsum(-jnp.sort(-cov))
    return _freq_misses_from_prefix(
        prefix, jnp.sum(cov), jnp.sum(cov > 0).astype(jnp.float32),
        capacity, pinned_retouches)


def _freq_misses_from_prefix(prefix, r, n, capacity, pinned_retouches):
    """Frequency-aware miss count given the descending-coverage prefix sums
    (``prefix[k-1]`` = mass of the k most-covered pages) — the O(P log P)
    sort is hoisted here so a knob grid over one shared stream pays it
    once, not once per candidate."""
    # clip before the int cast so huge float capacities stay well-defined
    cap = jnp.clip(jnp.asarray(capacity), 0, prefix.shape[0]).astype(jnp.int32)
    topc = jnp.where(cap > 0, prefix[jnp.maximum(cap - 1, 0)], 0.0)
    steady = r - topc
    pinned = r - jnp.asarray(pinned_retouches, jnp.float32)
    return jnp.clip(jnp.minimum(steady, pinned), n, r)


def sorted_scan_misses(
    policy: str,
    capacity,
    *,
    total_refs: float,
    distinct_pages: float,
    coverage: Optional[jnp.ndarray] = None,
    pinned_retouches: float = 0.0,
    min_capacity: int = 1,
) -> float:
    """Expected physical misses of a sorted one-pass probe stream.

    The policy-aware dispatch for sorted workloads (the single model behind
    ``CostSession`` Algorithm 1's sorted branch and the join planner's
    point-probe pricing):

    * ``capacity < min_capacity`` — the buffer cannot hold one probe window
      (Theorem III.1's capacity premise fails): every reference except the
      pressure-pinned window-junction re-touches misses,
      ``miss = R - pinned`` (thrash regime — junction re-touches survive
      even a capacity-1 buffer because no insertion separates them from the
      previous reference);
    * recency policies, ``capacity >= N``, or no coverage histogram — the
      compulsory closed form, ``miss = N`` (Theorem III.1: one compulsory
      miss per distinct page);
    * frequency-based policies below ``N`` — the frequency-aware closed form
      of :func:`_sorted_scan_misses_freq` on the window-coverage histogram.
    """
    r = float(total_refs)
    n = float(distinct_pages)
    if r <= 0.0:
        return 0.0
    if capacity is not None and capacity < min_capacity:
        return min(max(r - float(pinned_retouches), n), r)
    if (policy in RECENCY_POLICIES or coverage is None
            or capacity is None or capacity >= n):
        return n
    return float(_sorted_scan_misses_freq(jnp.asarray(coverage), capacity,
                                          pinned_retouches))


def sorted_scan_hit_rate(
    policy: str,
    capacity,
    *,
    total_refs: float,
    distinct_pages: float,
    coverage: Optional[jnp.ndarray] = None,
    pinned_retouches: float = 0.0,
    min_capacity: int = 1,
) -> float:
    """Hit rate of a sorted probe stream: ``(R - miss) / R``.

    Shares :func:`hit_rate_compulsory`'s zero-guards, so boundary estimates
    (R ~ 0, capacity at the thrash edge) agree everywhere — for recency
    policies above the capacity premise this IS ``hit_rate_compulsory``.
    """
    r = float(total_refs)
    if r <= 0.0:
        return 0.0
    miss = sorted_scan_misses(
        policy, capacity, total_refs=r, distinct_pages=distinct_pages,
        coverage=coverage, pinned_retouches=pinned_retouches,
        min_capacity=min_capacity)
    return (r - miss) / max(r, 1.0)


@functools.partial(jax.jit, static_argnames=("policy",))
def sorted_scan_hit_rate_grid(
    policy: str,
    coverage: jnp.ndarray,
    total_refs: jnp.ndarray,
    distinct_pages: jnp.ndarray,
    pinned_retouches: jnp.ndarray,
    capacities: jnp.ndarray,
    min_capacities: jnp.ndarray,
) -> jnp.ndarray:
    """Vmapped :func:`sorted_scan_hit_rate` for K sorted-stream candidates.

    The per-candidate dispatch (thrash / compulsory / frequency-aware)
    becomes branchless ``where`` selects so a whole knob grid solves in one
    pass — this is the sorted counterpart of the banded-matmul point/range
    grid kernels.

    Args:
      coverage:       window-coverage histogram(s): (P,) when every
                      candidate shares ONE stream (the common case — sorted
                      windows are eps-independent, only capacities and
                      ``min_capacities`` vary; the O(P log P) coverage sort
                      then runs once for the whole grid), or (K, P) when
                      index-backed candidates contribute distinct streams.
      total_refs:     (K,) request volumes R.
      distinct_pages: (K,) distinct page counts N.
      pinned_retouches: (K,) pressure-pinned window-junction re-touch
                      counts (see ``page_ref.sorted_workload_stats``).
      capacities:     (K,) buffer capacities in pages.
      min_capacities: (K,) Theorem III.1 capacity premises.

    Returns:
      (K,) hit rates.
    """
    r = jnp.asarray(total_refs, jnp.float32)
    n = jnp.asarray(distinct_pages, jnp.float32)
    # Regime dispatch compares in exact integer arithmetic (float32 rounds
    # page counts above 2^24); float32 stays for the miss-count values.
    cap_i = _exact_caps(capacities)
    n_i = _exact_caps(distinct_pages)
    if policy in RECENCY_POLICIES:
        miss = n
    else:
        cov = jnp.asarray(coverage, jnp.float32)
        pinned = jnp.asarray(pinned_retouches, jnp.float32)
        if cov.ndim == 1:
            prefix = jnp.cumsum(-jnp.sort(-cov))
            freq = jax.vmap(
                lambda rr, nn, cc, ss: _freq_misses_from_prefix(
                    prefix, rr, nn, cc, ss))(r, n, cap_i, pinned)
        else:
            freq = jax.vmap(_sorted_scan_misses_freq)(cov, cap_i, pinned)
        miss = jnp.where(cap_i >= n_i, n, freq)
    thrash = jnp.clip(r - jnp.asarray(pinned_retouches, jnp.float32), n, r)
    miss = jnp.where(cap_i < _exact_caps(min_capacities), thrash, miss)
    return jnp.where(r > 0, (r - miss) / jnp.maximum(r, 1.0), 0.0)


def sorted_scan_miss_curve(
    policy: str,
    capacities,
    *,
    total_refs: float,
    distinct_pages: float,
    coverage: Optional[jnp.ndarray] = None,
    pinned_retouches: float = 0.0,
    min_capacity: int = 1,
) -> jnp.ndarray:
    """Misses of ONE sorted stream as a function of buffer capacity.

    The miss-curve evaluation behind budget splitting: a join tree sharing
    one buffer pool needs every level's miss count at every candidate
    capacity, so this evaluates :func:`sorted_scan_misses` over a whole
    capacity vector in one vmapped solve (the stream statistics are shared,
    the O(P log P) coverage sort runs once — see
    :func:`sorted_scan_hit_rate_grid`, which this wraps with broadcast
    stats).  The curve is non-increasing in capacity: thrash (``miss = R``)
    below the Theorem III.1 premise, then the policy-aware regime, floored
    at the compulsory count N.

    Returns a (K,) miss vector aligned with ``capacities``.
    """
    caps = jnp.asarray(capacities)   # integer dtypes keep exact compares
    caps_f = caps.astype(jnp.float32)
    r = float(total_refs)
    if r <= 0.0:
        return jnp.zeros_like(caps_f)
    if policy not in RECENCY_POLICIES and coverage is not None:
        ones = jnp.ones_like(caps_f)
        h = sorted_scan_hit_rate_grid(
            policy, jnp.asarray(coverage, jnp.float32), r * ones,
            float(distinct_pages) * ones, float(pinned_retouches) * ones,
            caps, float(min_capacity) * ones)
        return (1.0 - h) * r
    # Recency policies (and coverage-less profiles) price through the
    # compulsory closed form; only the thrash edge depends on capacity.
    miss = jnp.full_like(caps_f, float(distinct_pages))
    thrash = min(max(r - float(pinned_retouches), float(distinct_pages)), r)
    return jnp.where(_exact_caps(caps) < int(min_capacity), thrash, miss)


def hit_rate_curve(
    policy: str,
    counts: jnp.ndarray,
    sample_refs: float,
    full_refs: float,
    capacities,
) -> jnp.ndarray:
    """Hit rate of ONE request histogram across a capacity vector.

    The IRM counterpart of :func:`sorted_scan_miss_curve`: K capacities of
    the SAME page-reference histogram solve as one vmapped lockstep
    bisection through :func:`hit_rate_grid` (compulsory closed form where
    ``C >= N``, zero below one page), so a budget-split solve never loops
    Python-side over candidate capacities.

    Returns a (K,) hit-rate vector aligned with ``capacities``.
    """
    caps = jnp.asarray(capacities)   # integer dtypes keep exact compares
    counts = jnp.asarray(counts, jnp.float32)
    ones = jnp.ones(caps.shape, jnp.float32)
    h, _ = hit_rate_grid(
        policy, jnp.broadcast_to(counts, caps.shape + counts.shape),
        float(sample_refs) * ones, float(full_refs) * ones, caps)
    return h


# ---------------------------------------------------------------------------
# Dispatcher
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("policy",))
def _hit_rate_jit(policy: str, probs, capacity):
    if policy == "lru":
        return hit_rate_lru(probs, capacity)
    if policy == "fifo":
        return hit_rate_fifo(probs, capacity)
    if policy == "lfu":
        return hit_rate_lfu(probs, capacity)
    raise ValueError(f"unknown policy {policy!r}")


def hit_rate(
    policy: str,
    capacity,
    probs: jnp.ndarray,
    *,
    total_requests: Optional[float] = None,
    distinct_pages: Optional[float] = None,
    sorted_workload: bool = False,
) -> jnp.ndarray:
    """Paper §III-B/§III-C dispatcher.

    * sorted workloads → Theorem III.1 closed form (NOTE: only exact for
      recency policies; policy-aware callers should use the
      ``sorted_scan_*`` family, which adds the frequency-aware form),
    * ``C >= N``       → compulsory-miss closed form,
    * otherwise        → the policy-specific IRM estimator.
    """
    probs = jnp.asarray(probs)
    n_distinct = (
        float(distinct_pages)
        if distinct_pages is not None
        else float(jnp.sum(probs > 0))
    )
    if sorted_workload or (capacity is not None and float(capacity) >= n_distinct):
        if total_requests is None:
            raise ValueError("closed-form hit rate needs total_requests (R)")
        return hit_rate_compulsory(total_requests, n_distinct)
    if policy not in POLICIES:
        raise ValueError(f"unknown policy {policy!r}; expected one of {POLICIES}")
    return _hit_rate_jit(policy, probs, capacity)


# ---------------------------------------------------------------------------
# Batched grid solver (CostSession.estimate_grid)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("policy",))
def hit_rate_grid(
    policy: str,
    counts: jnp.ndarray,
    sample_refs: jnp.ndarray,
    full_refs: jnp.ndarray,
    capacities: jnp.ndarray,
    sorted_coverage: Optional[jnp.ndarray] = None,
    sorted_refs: Optional[jnp.ndarray] = None,
    sorted_distinct: Optional[jnp.ndarray] = None,
    sorted_pinned: Optional[jnp.ndarray] = None,
    sorted_min_caps: Optional[jnp.ndarray] = None,
    sorted_full_refs: Optional[jnp.ndarray] = None,
    write_counts: Optional[jnp.ndarray] = None,
    write_refs: Optional[jnp.ndarray] = None,
    write_full_refs: Optional[jnp.ndarray] = None,
):
    """Hit rates for K (histogram, capacity) candidates in one vmapped solve.

    The per-candidate dispatch of :func:`hit_rate` (compulsory closed form
    when ``C >= N``, zero when ``C < 1``, policy fixed point otherwise)
    becomes branchless ``where`` selects so the whole knob grid solves under
    a single jit — K bisections run lockstep instead of K Python round trips.

    When the ``sorted_*`` arguments are given (mixed workloads containing
    sorted probe streams), each candidate's IRM estimate is composed with the
    policy-aware sorted-scan model (:func:`sorted_scan_hit_rate_grid`) by
    expected-miss addition over a shared buffer — the same composition
    ``CostSession._finish`` applies per candidate.

    Args:
      counts:      (K, P) expected page-reference histograms (IRM parts).
      sample_refs: (K,) sample request mass (normalizer of Pr_req).
      full_refs:   (K,) full-workload request volume R (compulsory branch).
      capacities:  (K,) buffer capacities in pages (may be <= 0).
      sorted_coverage / sorted_refs / sorted_distinct / sorted_pinned /
      sorted_min_caps: per-candidate sorted-stream statistics, shapes as in
        :func:`sorted_scan_hit_rate_grid`.
      sorted_full_refs: (K,) full-workload sorted request volume (CAM-x
        scaling of the sorted part's expected misses).
      write_counts / write_refs / write_full_refs: per-candidate write-stream
        histograms ((K, P)), sample write mass and full write volume.  Write
        references are COMBINED into the request histogram before the solve
        (a write faults its target page like a read), and the dirty-eviction
        writeback stream (:func:`writeback_fraction`) is subtracted from the
        hit rate, so ``(1 - h) * E[DAC]`` prices fetches AND flushes of the
        read/write mix in one number.  The returned ``h`` may be slightly
        negative at tiny capacities (a write can cost fetch + flush > 1 I/O
        per reference) — by construction, not by error.

    Returns:
      (hit_rates (K,), distinct_pages (K,)) — pages with nonzero mass in
      either the IRM histogram or the sorted coverage.
    """
    if policy == "lru":
        fn = hit_rate_lru
    elif policy == "fifo":
        fn = hit_rate_fifo
    elif policy == "lfu":
        fn = hit_rate_lfu
    else:
        raise ValueError(f"unknown policy {policy!r}; expected one of {POLICIES}")
    has_write = write_counts is not None
    if has_write:
        # writes fault their target page exactly like reads: fold the write
        # stream into the request histogram so misses price automatically,
        # then add the dirty-eviction flush stream below.
        counts = counts + write_counts
        sample_refs = sample_refs + jnp.asarray(write_refs,
                                                jnp.asarray(sample_refs).dtype)
        full_refs = full_refs + jnp.asarray(write_full_refs,
                                            jnp.asarray(full_refs).dtype)
    probs = counts / jnp.maximum(sample_refs[:, None], 1e-30)
    n_distinct_i = jnp.sum(counts > 0, axis=1)
    n_distinct = n_distinct_i.astype(jnp.float32)
    cap_f = capacities.astype(jnp.float32)
    # exact integer compares for the regime dispatch (float32 rounds page
    # counts above 2^24); the fixed-point solve itself stays float32 — it
    # only runs below n_distinct, far under the rounding threshold.
    cap_i = _exact_caps(capacities)
    h_policy = jax.vmap(lambda p, c: fn(p, jnp.maximum(c, 1.0)))(probs, cap_f)
    floor = jnp.zeros_like(h_policy)
    if has_write:
        wprobs = write_counts / jnp.maximum(sample_refs[:, None], 1e-30)
        wb = jax.vmap(lambda p, w, c: _writeback_terms(
            policy, p, w, jnp.maximum(c, 1.0)))(probs, wprobs, cap_f)
        h_policy = h_policy - wb
        floor = -jnp.sum(wprobs, axis=1)  # cap < 1: every write flushes
    h_comp = hit_rate_compulsory(full_refs, n_distinct)
    h = jnp.where(cap_i >= n_distinct_i, h_comp, h_policy)
    h = jnp.where(cap_i < 1, floor, h)
    h = jnp.where(jnp.asarray(sample_refs, jnp.float32) > 0, h, 0.0)
    if sorted_coverage is None:
        return h, n_distinct
    h_s = sorted_scan_hit_rate_grid(
        policy, sorted_coverage, sorted_refs, sorted_distinct, sorted_pinned,
        capacities, sorted_min_caps)
    s_full = jnp.asarray(sorted_full_refs, jnp.float32)
    total_full = full_refs + s_full
    miss = (1.0 - h) * full_refs + (1.0 - h_s) * s_full
    h_mix = jnp.where(total_full > 0,
                      1.0 - miss / jnp.maximum(total_full, 1.0), 0.0)
    n_mix = jnp.sum((counts > 0) | (sorted_coverage > 0),
                    axis=1).astype(jnp.float32)
    return h_mix, n_mix
