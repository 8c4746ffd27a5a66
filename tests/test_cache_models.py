"""Hit-rate model tests: fixed-point consistency, IRM validation vs replay,
and the sorted-workload theorem (exact, property-based)."""
import numpy as np
import jax.numpy as jnp
import pytest
from _hyp import given, settings, st

from repro.core import cache_models as cm
from repro.core import page_ref
from repro.core import replay


def zipf_probs(n, a=1.2, seed=0):
    p = 1.0 / np.arange(1, n + 1) ** a
    rng = np.random.default_rng(seed)
    rng.shuffle(p)
    return p / p.sum()


# ---------------------------------------------------------------------------
# Fixed-point consistency
# ---------------------------------------------------------------------------

def _consistency_ref(policy, probs, cap):
    """Characteristic time by float64 bisection on the host: Che's Eq. 8
    for LRU, Fricker's Eq. 5 for FIFO."""
    p = np.asarray(probs, np.float64)

    def occupancy(t):
        if policy == "lru":
            return np.sum(-np.expm1(-p * t))
        return np.sum(p * t / (1.0 - p + p * t))

    lo, hi = 0.0, 1.0
    while occupancy(hi) < cap:
        hi *= 2.0
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if occupancy(mid) < cap else (lo, mid)
    return 0.5 * (lo + hi)


@pytest.mark.parametrize("cap_frac", [0.05, 0.3, 0.7])
@pytest.mark.parametrize("a", [0.9, 1.1, 1.3])
@pytest.mark.parametrize("policy", ["lru", "fifo"])
def test_fixed_point_consistency(policy, a, cap_frac):
    """The characteristic time solves its consistency condition (LRU: Che's
    sum of 1 - exp(-p T) = C; FIFO: Fricker's occupancy sum = C) to 1e-3,
    and agrees with a float64 host bisection to 1e-3 relative."""
    probs = jnp.asarray(zipf_probs(5000, a=a), jnp.float32)
    cap = cap_frac * 5000
    if policy == "lru":
        t = cm.solve_che_time(probs, cap)
        occ = -jnp.expm1(-probs * t)
    else:
        t = cm.solve_fifo_tau(probs, cap)
        occ = probs * t / (1.0 - probs + probs * t)
    assert abs(float(jnp.sum(occ)) - cap) / cap < 1e-3
    t_ref = _consistency_ref(policy, probs, cap)
    assert abs(float(t) - t_ref) / t_ref < 1e-3, (float(t), t_ref)


def test_hit_rates_bounded_and_ordered():
    """LFU >= LRU >= FIFO under IRM for skewed popularity (classic result)."""
    probs = jnp.asarray(zipf_probs(2000, a=1.5), jnp.float32)
    cap = 200
    h_lfu = float(cm.hit_rate_lfu(probs, cap))
    h_lru = float(cm.hit_rate_lru(probs, cap))
    h_fifo = float(cm.hit_rate_fifo(probs, cap))
    for h in (h_lfu, h_lru, h_fifo):
        assert 0.0 <= h <= 1.0
    assert h_lfu >= h_lru - 1e-3
    assert h_lru >= h_fifo - 1e-3


def test_uniform_popularity_all_policies_equal():
    n, cap = 1000, 100
    probs = jnp.full((n,), 1.0 / n, jnp.float32)
    for fn in (cm.hit_rate_lru, cm.hit_rate_fifo, cm.hit_rate_lfu):
        assert abs(float(fn(probs, cap)) - cap / n) < 0.02


# ---------------------------------------------------------------------------
# IRM estimators vs actual replay of an IID trace
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cap_share", [0.05, 0.15, 0.4])
@pytest.mark.parametrize("a", [0.9, 1.1, 1.3])
@pytest.mark.parametrize("policy", ["lru", "fifo", "lfu"])
def test_irm_estimate_matches_iid_replay(policy, a, cap_share):
    n_pages, n_refs = 2000, 120_000
    cap = int(cap_share * n_pages)
    probs = zipf_probs(n_pages, a=a, seed=1)
    rng = np.random.default_rng(2)
    trace = rng.choice(n_pages, size=n_refs, p=probs)
    hits, misses = replay.replay_refs(trace, cap, policy)
    actual = hits / n_refs
    est = float(cm.hit_rate(policy, cap, jnp.asarray(probs, jnp.float32),
                            total_requests=n_refs))
    # LFU converges slowly on finite traces (paper §VII-C caveat) — wider tol.
    tol = 0.08 if policy == "lfu" else 0.03
    assert abs(est - actual) < tol, (policy, est, actual)


@pytest.mark.parametrize("a", [0.9, 1.1, 1.3])
@pytest.mark.parametrize("policy", ["lru", "fifo", "lfu"])
def test_hit_rate_grid_matches_per_capacity_dispatch(policy, a):
    """One vmapped ``hit_rate_grid`` over a capacity vector equals
    ``hit_rate`` called once per capacity: the policy fixed point below N
    distinct pages, the compulsory closed form at and above it (N counts
    only pages with nonzero mass)."""
    n_pages, n_refs = 2000, 50_000.0
    probs = zipf_probs(n_pages, a=a, seed=3)
    probs[np.random.default_rng(4).choice(n_pages, 200, replace=False)] = 0.0
    probs /= probs.sum()
    n_distinct = int(np.sum(probs > 0))
    caps = np.array([1, 7, 60, 450, 1200, n_distinct - 1, n_distinct,
                     n_pages + 500], np.int32)
    counts = np.asarray(probs * n_refs, np.float32)
    k = len(caps)
    h_grid, nd = cm.hit_rate_grid(
        policy, jnp.broadcast_to(jnp.asarray(counts), (k, n_pages)),
        jnp.full((k,), n_refs, jnp.float32),
        jnp.full((k,), n_refs, jnp.float32), jnp.asarray(caps))
    assert np.all(np.asarray(nd) == n_distinct)
    p32 = jnp.asarray(counts / np.float32(n_refs), jnp.float32)
    for i, cap in enumerate(caps):
        h_one = float(cm.hit_rate(policy, int(cap), p32,
                                  total_requests=n_refs))
        assert abs(float(h_grid[i]) - h_one) < 1e-5, (policy, cap)


def test_compulsory_case_large_capacity():
    n_pages, n_refs = 500, 20_000
    probs = zipf_probs(n_pages, a=1.1, seed=3)
    rng = np.random.default_rng(4)
    trace = rng.choice(n_pages, size=n_refs, p=probs)
    distinct = len(np.unique(trace))
    hits, _ = replay.replay_refs(trace, capacity=n_pages + 10, policy="lru")
    est = float(cm.hit_rate("lru", n_pages + 10, jnp.asarray(probs, jnp.float32),
                            total_requests=n_refs, distinct_pages=distinct))
    assert abs(est - hits / n_refs) < 1e-6


# ---------------------------------------------------------------------------
# Theorem III.1 — sorted workloads: h == (R - N)/R, policy-independent, EXACT
# ---------------------------------------------------------------------------

def _sorted_windows(eps, c_ipp, n_queries, seed, n=50_000):
    rng = np.random.default_rng(seed)
    pos = np.sort(rng.integers(0, n, size=n_queries))
    pred = np.clip(pos + rng.integers(-eps, eps + 1, size=n_queries), 0, n - 1)
    lo = np.clip(pred - eps, 0, n - 1) // c_ipp
    hi = np.clip(pred + eps, 0, n - 1) // c_ipp
    # windows of a sorted query stream: enforce monotone window starts, as in
    # the theorem statement (learned-index windows over sorted keys are).
    lo = np.maximum.accumulate(lo)
    hi = np.maximum(hi, lo)
    R = int(np.sum(hi - lo + 1))
    distinct = set()
    for a, b in zip(lo, hi):
        distinct.update(range(a, b + 1))
    return lo, hi, R, len(distinct)


@settings(max_examples=25, deadline=None)
@given(
    st.integers(min_value=1, max_value=64),      # eps
    st.integers(min_value=4, max_value=64),      # c_ipp
    st.integers(min_value=20, max_value=300),    # queries
    st.booleans(),                               # lru vs fifo
    st.integers(min_value=0, max_value=10_000),  # seed
)
def test_sorted_workload_theorem_exact_lru_fifo(eps, c_ipp, n_queries, use_lru, seed):
    policy = "lru" if use_lru else "fifo"
    lo, hi, R, N = _sorted_windows(eps, c_ipp, n_queries, seed)
    capacity = 1 + int(np.ceil(2 * eps / c_ipp))
    misses = replay.replay_windows(lo, hi, capacity, policy)
    assert misses.sum() == N  # exactly one compulsory miss per distinct page
    h_actual = (R - misses.sum()) / R
    h_thm = float(cm.hit_rate_compulsory(R, N))
    assert abs(h_actual - h_thm) < 1e-6


@settings(max_examples=25, deadline=None)
@given(
    st.integers(min_value=1, max_value=64),
    st.integers(min_value=4, max_value=64),
    st.integers(min_value=20, max_value=300),
    st.integers(min_value=0, max_value=10_000),
)
def test_sorted_workload_theorem_lfu_caveat(eps, c_ipp, n_queries, seed):
    """FINDING (recorded in EXPERIMENTS.md): Thm III.1 claims policy
    independence, but its proof step "no page in W_t can be evicted before
    pi_t finishes" fails for LFU at the minimal capacity — a stale
    high-frequency page pins itself and LFU evicts the freq-1 in-window page
    (hypothesis found concrete counterexamples, e.g. eps=1, c_ipp=4).  The
    theorem IS a valid lower bound for LFU, and exact given C >= N slack."""
    lo, hi, R, N = _sorted_windows(eps, c_ipp, n_queries, seed)
    capacity = 1 + int(np.ceil(2 * eps / c_ipp))
    misses = replay.replay_windows(lo, hi, capacity, "lfu").sum()
    assert misses >= N                     # compulsory lower bound always holds
    misses_big = replay.replay_windows(lo, hi, N + 1, "lfu").sum()
    assert misses_big == N                 # exact once capacity has slack


def _coverage(lo, hi, num_pages):
    diff = (np.bincount(lo, minlength=num_pages + 1)[:num_pages]
            - np.bincount(hi + 1, minlength=num_pages + 2)[:num_pages])
    return np.cumsum(diff).astype(np.float64)


# ---------------------------------------------------------------------------
# sorted_scan family — the policy-aware sorted-stream model
# ---------------------------------------------------------------------------

def test_sorted_scan_dispatch_regimes():
    lo, hi, R, N = _sorted_windows(16, 8, 400, seed=11)
    num_pages = int(hi.max()) + 1
    cov = _coverage(lo, hi, num_pages)
    kw = dict(total_refs=float(R), distinct_pages=float(N), coverage=cov)
    # thrash: below the Theorem III.1 capacity premise, every ref misses
    assert cm.sorted_scan_misses("lru", 2, min_capacity=5, **kw) == R
    assert cm.sorted_scan_hit_rate("lfu", 2, min_capacity=5, **kw) == 0.0
    # recency policies: compulsory closed form at ANY capacity above premise
    for pol in cm.RECENCY_POLICIES:
        assert cm.sorted_scan_misses(pol, 10, **kw) == N
    # LFU with capacity slack: compulsory too (buffer never evicts a window)
    assert cm.sorted_scan_misses("lfu", N + 5, **kw) == N
    # LFU below N: frequency-aware, bracketed by [N, R], monotone in capacity
    small = cm.sorted_scan_misses("lfu", max(2, N // 10), **kw)
    mid = cm.sorted_scan_misses("lfu", max(3, N // 2), **kw)
    assert N <= mid <= small <= R
    # without a coverage histogram the model degrades to the recency form
    assert cm.sorted_scan_misses(
        "lfu", 10, total_refs=float(R), distinct_pages=float(N)) == N


def test_sorted_scan_zero_guards_match_compulsory():
    """Satellite fix: _finish's old inline form used max(r, 1e-30); the
    shared model must use hit_rate_compulsory's guards everywhere."""
    assert cm.sorted_scan_hit_rate("lru", 8, total_refs=0.0,
                                   distinct_pages=0.0) == 0.0
    assert float(cm.hit_rate_compulsory(0.0, 0.0)) == 0.0
    for r, n in [(0.5, 0.25), (1.0, 1.0), (100.0, 7.0)]:
        assert abs(cm.sorted_scan_hit_rate("fifo", 1_000_000, total_refs=r,
                                           distinct_pages=n)
                   - float(cm.hit_rate_compulsory(r, n))) < 1e-7


@settings(max_examples=15, deadline=None)
@given(
    st.integers(min_value=1, max_value=64),
    st.integers(min_value=4, max_value=64),
    st.integers(min_value=50, max_value=300),
    st.integers(min_value=0, max_value=10_000),
)
def test_sorted_scan_lfu_model_bracketed_vs_replay(eps, c_ipp, n_queries, seed):
    """The frequency-aware form stays within the physical bracket [N, R]
    and never under-cuts the compulsory bound that LFU replay obeys."""
    lo, hi, R, N = _sorted_windows(eps, c_ipp, n_queries, seed)
    num_pages = int(hi.max()) + 1
    cov = _coverage(lo, hi, num_pages)
    cap = max(2, N // 3)
    miss = cm.sorted_scan_misses("lfu", cap, total_refs=float(R),
                                 distinct_pages=float(N), coverage=cov)
    assert N - 1e-3 <= miss <= R + 1e-3
    actual = replay.replay_windows(lo, hi, cap, "lfu").sum()
    assert actual >= N                      # replay obeys the same floor


def test_sorted_scan_grid_matches_scalar():
    lo, hi, R, N = _sorted_windows(32, 16, 500, seed=4)
    num_pages = int(hi.max()) + 1
    cov = jnp.asarray(_coverage(lo, hi, num_pages), jnp.float32)
    caps = np.array([1, 3, 10, N // 2, N + 10], np.float64)
    min_caps = np.full_like(caps, 3.0)
    pinned = 7.0
    for policy in ("lru", "fifo", "lfu"):
        h_grid = np.asarray(cm.sorted_scan_hit_rate_grid(
            policy, jnp.broadcast_to(cov, (len(caps),) + cov.shape),
            jnp.full((len(caps),), float(R)), jnp.full((len(caps),), float(N)),
            jnp.full((len(caps),), pinned), jnp.asarray(caps, jnp.float32),
            jnp.asarray(min_caps, jnp.float32)))
        for i, cap in enumerate(caps):
            h_ref = cm.sorted_scan_hit_rate(
                policy, cap, total_refs=float(R), distinct_pages=float(N),
                coverage=cov, pinned_retouches=pinned, min_capacity=3)
            assert abs(float(h_grid[i]) - h_ref) < 1e-5, (policy, cap)


def test_sorted_scan_lfu_pinned_correction_vs_replay():
    """Satellite fix: the pressure-pinned junction bound removes the ~2x
    LFU over-prediction on strongly recency-like narrow-window streams at
    small capacities (width-2 sliding windows, dense jittered width-1/2
    streams), while never under-cutting replay on those streams."""
    streams = []
    # width-2 stride-1 sliding windows: the canonical over-prediction case
    lo = np.arange(4000, dtype=np.int64)
    streams.append(("slide-w2", lo, lo + 1, [8, 64, 256]))
    # dense jittered width-1/2 stream (many probes per page)
    rng = np.random.default_rng(3)
    pos = np.sort(rng.integers(0, 20_000, size=8000))
    dlo = np.clip(pos - 2, 0, 19_999) // 16
    dhi = np.clip(pos + 2, 0, 19_999) // 16
    dlo = np.maximum.accumulate(dlo)
    streams.append(("dense-jitter", dlo, np.maximum(dhi, dlo), [4, 16, 64]))
    for name, slo, shi, caps in streams:
        num_pages = int(shi.max()) + 1
        r, n, cov, pinned = page_ref.sorted_workload_stats(
            jnp.asarray(slo, jnp.int32), jnp.asarray(shi, jnp.int32),
            num_pages)
        for cap in caps:
            actual = float(replay.replay_windows(slo, shi, cap, "lfu").sum())
            pred = cm.sorted_scan_misses(
                "lfu", cap, total_refs=float(r), distinct_pages=float(n),
                coverage=cov, pinned_retouches=float(pinned),
                min_capacity=int((shi - slo + 1).max()))
            q = max(pred / actual, actual / pred)
            assert q < 1.25, (name, cap, pred, actual)
            # junction re-touches are guaranteed hits: never under-predict
            assert pred >= actual - 1e-6, (name, cap, pred, actual)


def test_lemma_iv1_sorted_order_minimizes_misses():
    """Sorted probe order attains the compulsory-miss lower bound; random
    permutations can only do worse (Lemma IV.1)."""
    rng = np.random.default_rng(7)
    eps, c_ipp = 16, 8
    n = 20_000
    pos = np.sort(rng.integers(0, n, size=400))
    lo = np.clip(pos - eps, 0, n - 1) // c_ipp
    hi = np.clip(pos + eps, 0, n - 1) // c_ipp
    cap = 1 + int(np.ceil(2 * eps / c_ipp))
    sorted_misses = replay.replay_windows(lo, hi, cap, "lru").sum()
    for _ in range(5):
        perm = rng.permutation(len(pos))
        perm_misses = replay.replay_windows(lo[perm], hi[perm], cap, "lru").sum()
        assert perm_misses >= sorted_misses


def test_sorted_scan_capacity_compares_exact_above_float32():
    """Regression: page-count regime compares must be exact above 2^24.

    float32 rounds 2^24 + 1 down to 2^24, so a rounded compare would put a
    16777216-page buffer AT (not below) a 16777217-page Theorem III.1
    premise and silently skip the thrash regime at large capacities; the
    exact int32 compare path must keep the one-page distinction.
    """
    r, n = float(2**25), float(2**24)
    cov = jnp.ones((8,), jnp.float32)           # unused under recency
    caps = np.array([2**24, 2**24 + 1], np.int64)
    min_caps = np.full(2, 2**24 + 1, np.int64)
    for policy in ("lru", "fifo", "lfu"):
        h = np.asarray(cm.sorted_scan_hit_rate_grid(
            policy, jnp.broadcast_to(cov, (2, 8)),
            jnp.full((2,), r), jnp.full((2,), n), jnp.zeros((2,)),
            jnp.asarray(caps), jnp.asarray(min_caps)))
        assert h[0] == 0.0, (policy, h)         # one page short: thrash
        assert h[1] == pytest.approx(0.5), (policy, h)   # at the premise
