"""Fused PriceTable solve: policy fixed point + sorted/mixed composition +
objective argmin in ONE pallas launch (the DeviceExecutor hot path).

Prices K histograms x C capacities with one HBM pass per histogram: each
grid program loads ONE profile row's popularity histogram into VMEM and
prices ALL of that row's table cells against it — the Che/Fricker
bisection (or the LFU top-C mass) runs lockstep over the row's C
capacities as (C, P) VPU work on the resident block, the policy-aware
sorted-scan model and the mixed composition of
``cache_models.hit_rate_grid`` apply in place, and each program folds its
row's objective minimum into a revisited SMEM scalar accumulator with a
lowest-cell-id tie-break.  A (knob x split x capacity) table therefore
prices in a single launch — one HBM pass over the histograms, no
per-stage XLA round trips.

Semantics mirror ``cache_models.hit_rate_grid`` branch for branch
(compulsory closed form where ``cap >= N`` in exact int32 compares, zero
below one page, thrash/frequency/compulsory sorted regimes, expected-miss
composition); equivalence is float32-tolerance only (summation order),
pinned by tests/test_engine.py against the host executor.

On a TPU v5e the kernel compiles (tests/test_tpu_compile.py) with every
row and cell resident in VMEM, so P is bounded.  With C <= 128 cells per
row, every mode compiles at P <= ``V5E_MAX_PAGES`` = 32,768 pages; all
but multi-policy launches with writes also at 65,536, and plain lru,
fifo, lfu and multi at 131,072 (the largest tried).  Compile time grows
with C * P (for multi, about 1.5 min at 65,536 and 3 min at 131,072).
Past that the solve needs a P-tiled reduction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["price_grid", "PAD_ID", "V5E_MAX_PAGES"]

_LANES = 128
#: Cell id marking a padded (row, slot) cell; valid ids are always below it.
PAD_ID = 2**31 - 1

_F32_COLS = 16   # packed per-row float32 scalars (see _price_kernel)
_I32_COLS = 8    # packed per-row int32 scalars

# scoped VMEM: half of v5e's 128 MiB; raising it further admits no larger P
_VMEM_LIMIT_BYTES = 64 * 2**20
#: Largest page count every mode compiles at on v5e (C <= 128 cells/row).
V5E_MAX_PAGES = 32_768

# |x| below which _expm1 sums its Taylor series: the degree-8 remainder is
# under 1e-8 relative there, and above it exp(x) - 1 loses at most ~3 ulp
_EXPM1_SERIES = 0.5


def _expm1(x):
    """``exp(x) - 1`` from ops Mosaic lowers, accurate for small ``|x|``.

    Mosaic has no ``expm1``; plain ``exp(x) - 1`` cancels catastrophically
    for the small ``p * t`` of cold pages, so small arguments take a
    Horner-form Taylor series instead.
    """
    series = jnp.ones_like(x)
    for n in range(8, 1, -1):
        series = 1.0 + series * x / n
    return jnp.where(jnp.abs(x) < _EXPM1_SERIES, x * series, jnp.exp(x) - 1.0)


def _price_kernel(*refs, policy: str, has_sorted: bool, has_write: bool,
                  iters: int, n_in: int):
    """One program = one profile row priced at all its C cells.

    ``policy`` is one of the static ``cache_models.POLICIES`` (the whole
    launch shares one fixed point) or ``"multi"``: each program reads its
    OWN policy id from i32 column 3 (``POLICIES`` order: 0 lru, 1 fifo,
    2 lfu) and selects between the recency bisection and the LFU top-C
    mass — one launch pricing a multi-policy table side by side.

    With ``has_write`` the row's probabilities are the COMBINED read+write
    request stream (the executor folds them before normalizing, mirroring
    ``hit_rate_grid``); the kernel additionally prices the dirty-eviction
    writeback stream at the SAME characteristic time the read fixed point
    already solved — no second bisection — and subtracts it from ``h``, so
    ``(1 - h)`` counts fetches and flushes together.

    Packed scalar columns (one row each per program):
      f32: 0 sample_refs, 1 full_refs, 2 n_distinct, 3 pmin,
           4 sorted_refs, 5 sorted_full_refs, 6 sorted_distinct,
           7 sorted_pinned, 8 objective_scale
      i32: 0 n_distinct, 1 sorted_distinct, 2 sorted_min_capacity,
           3 policy id (read iff policy == "multi")
    """
    ins, outs = refs[:n_in], refs[n_in:]
    it = iter(ins)
    lfu_read = policy in ("lfu", "multi")
    p = next(it)[...]                                       # (1, P) probs
    sp = next(it)[...] if lfu_read else None                # (1, P) desc
    cov = (next(it)[...] if (has_sorted and lfu_read)
           else None)                                       # (1, P) desc
    w = next(it)[...] if has_write else None                # (1, P) wprobs
    wq = (next(it)[...] if (has_write and lfu_read)
          else None)                                        # (1, P) by -p
    f = next(it)[...]                                       # (1, 16) f32
    z = next(it)[...]                                       # (1, 8) i32
    caps_f = next(it)[...]                                  # (1, C)
    caps_i = next(it)[...]                                  # (1, C)
    ids = next(it)[...]                                     # (1, C)
    h_ref, bv_ref, bi_ref = outs

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        bv_ref[0, 0] = jnp.float32(jnp.inf)
        bi_ref[0, 0] = jnp.int32(PAD_ID)

    sample_refs, full, n_f, pmin = f[0, 0], f[0, 1], f[0, 2], f[0, 3]
    n_i = z[0, 0]
    c_eff = jnp.maximum(caps_f, 1.0)                        # (1, C)
    c_t = c_eff.T                                           # (C, 1)

    # -- policy fixed point, lockstep over the row's C capacities ----------
    pol_id = z[0, 3] if policy == "multi" else None
    if policy in ("lru", "fifo", "multi"):
        hi = jnp.maximum(4.0 * c_t / pmin, 1.0)
        lo = jnp.zeros_like(hi)

        def occ(t):                                         # (C, 1) -> (C, P)
            if policy == "lru":
                return -_expm1(-p * t)
            if policy == "fifo":
                return p * t / (1.0 - p + p * t)
            # multi: per-program scalar select between the recency forms
            # (the bisected objective stays monotone either way)
            return jnp.where(pol_id == 0, -_expm1(-p * t),
                             p * t / (1.0 - p + p * t))

        def body(_, st):
            lo, hi = st
            mid = 0.5 * (lo + hi)
            val = jnp.sum(occ(mid), axis=1, keepdims=True) - c_t
            lo = jnp.where(val < 0.0, mid, lo)
            hi = jnp.where(val < 0.0, hi, mid)
            return lo, hi

        lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
        t_c = 0.5 * (lo + hi)
        h_pol = jnp.sum(p * occ(t_c), axis=1, keepdims=True).T   # (1, C)
    if policy in ("lfu", "multi"):                          # lfu: top-C mass
        iota = jax.lax.broadcasted_iota(jnp.int32, (caps_i.shape[1],
                                                    p.shape[1]), 1)
        mask = iota < jnp.maximum(caps_i, 1).T              # (C, P)
        h_lfu = jnp.sum(jnp.where(mask, sp, 0.0), axis=1,
                        keepdims=True).T
        h_pol = (h_lfu if policy == "lfu"
                 else jnp.where(pol_id == 2, h_lfu, h_pol))

    floor = 0.0
    if has_write:
        # dirty-eviction writeback at the SAME t_c / top-C set the read
        # solve produced (cache_models._writeback_terms, lockstep over C)
        w_mass = jnp.sum(w)
        if policy in ("lru", "fifo", "multi"):
            r = jnp.maximum(p - w, 0.0)
            dirty = w + r * -_expm1(-w * t_c)               # (C, P)
            wb = jnp.sum((1.0 - occ(t_c)) * dirty, axis=1,
                         keepdims=True).T                   # (1, C)
        if lfu_read:
            wiota = jax.lax.broadcasted_iota(
                jnp.int32, (caps_i.shape[1], p.shape[1]), 1)
            kept = jnp.sum(jnp.where(wiota < jnp.maximum(caps_i, 1).T,
                                     wq, 0.0), axis=1, keepdims=True).T
            wb_lfu = w_mass - kept
            wb = (wb_lfu if policy == "lfu"
                  else jnp.where(pol_id == 2, wb_lfu, wb))
        h_pol = h_pol - wb
        floor = -w_mass                 # cap < 1: every write flushes

    h_comp = jnp.where(full > 0, (full - n_f) / jnp.maximum(full, 1.0), 0.0)
    h = jnp.where(caps_i >= n_i, h_comp, h_pol)
    h = jnp.where(caps_i < 1, floor, h)
    h = jnp.where(sample_refs > 0, h, 0.0)

    # -- sorted-scan model + mixed composition (hit_rate_grid tail) --------
    if has_sorted:
        s_r, s_full, s_n, pinned = f[0, 4], f[0, 5], f[0, 6], f[0, 7]
        s_n_i, s_min_i = z[0, 1], z[0, 2]
        if policy in ("lru", "fifo"):
            miss = jnp.zeros_like(caps_f) + s_n
        else:
            iota = jax.lax.broadcasted_iota(jnp.int32, (caps_i.shape[1],
                                                        p.shape[1]), 1)
            topc = jnp.sum(jnp.where(iota < caps_i.T, cov, 0.0), axis=1,
                           keepdims=True).T
            freq = jnp.clip(jnp.minimum(s_r - topc, s_r - pinned), s_n, s_r)
            miss = jnp.where(caps_i >= s_n_i, s_n, freq)
            if policy == "multi":   # recency rows take the compulsory form
                miss = jnp.where(pol_id == 2, miss,
                                 jnp.zeros_like(caps_f) + s_n)
        thrash = jnp.clip(s_r - pinned, s_n, s_r)
        miss = jnp.where(caps_i < s_min_i, thrash, miss)
        h_s = jnp.where(s_r > 0, (s_r - miss) / jnp.maximum(s_r, 1.0), 0.0)
        total = full + s_full
        miss_mix = (1.0 - h) * full + (1.0 - h_s) * s_full
        h = jnp.where(total > 0, 1.0 - miss_mix / jnp.maximum(total, 1.0),
                      0.0)

    h_ref[...] = h

    # -- objective + argmin folded into the revisited accumulator tile -----
    obj = jnp.where(ids < PAD_ID, (1.0 - h) * f[0, 8], jnp.inf)
    minv = jnp.min(obj)
    minid = jnp.min(jnp.where(obj == minv, ids, jnp.int32(PAD_ID)))
    prev_v, prev_i = bv_ref[0, 0], bi_ref[0, 0]
    better = (minv < prev_v) | ((minv == prev_v) & (minid < prev_i))
    bv_ref[0, 0] = jnp.where(better, minv, prev_v)
    bi_ref[0, 0] = jnp.where(better, minid, prev_i)


@functools.partial(jax.jit, static_argnames=("policy", "has_sorted",
                                             "has_write", "iters",
                                             "interpret"))
def price_grid(policy: str, probs, sorted_probs, cov_desc, f32s, i32s,
               caps_f, caps_i, ids, wprobs=None, wprobs_q=None, *,
               has_sorted: bool, has_write: bool = False, iters: int = 64,
               interpret: bool = False):
    """Price a (K rows x C cells-per-row) padded table in one launch.

    Args:
      policy: a ``cache_models.POLICIES`` name (uniform launch) or
        ``"multi"`` — each row reads its own policy id from i32 column 3,
        so one launch prices lru/fifo/lfu rows side by side.
      probs: (K, P) float32 request probabilities per profile row —
        COMBINED read+write stream when ``has_write`` (the caller folds
        write counts into the histogram before normalizing).
      sorted_probs: (K, P) descending-sorted ``probs`` (read iff lfu or
        multi).
      cov_desc: (K, P) descending-sorted sorted-scan coverage (read iff
        (lfu or multi) AND ``has_sorted``).
      f32s / i32s: (K, 16) / (K, 8) packed per-row scalars (layout in
        :func:`_price_kernel`).
      caps_f / caps_i / ids: (K, C) per-cell capacities (float32 /
        exact int32) and global cell ids; padded cells carry
        ``caps_i = -1`` and ``ids = PAD_ID``.
      wprobs: (K, P) write-reference probabilities under the SAME combined
        normalizer (read iff ``has_write``).
      wprobs_q: (K, P) ``wprobs`` permuted by descending combined ``probs``
        (the LFU resident set's order; read iff ``has_write`` and lfu or
        multi).

    Returns:
      (h (K, C) float32, best_val (1, 1) float32, best_id (1, 1) int32) —
      ``best_id`` is the global objective argmin over valid cells
      (lowest id on ties, i.e. first cell in table order).
    """
    k, p_width = probs.shape
    c = caps_f.shape[1]
    if has_write and wprobs is None:
        raise ValueError("has_write=True needs wprobs (and wprobs_q for "
                         "lfu/multi launches)")
    pad_p = (-p_width) % _LANES
    pad_c = (-c) % _LANES
    if pad_p:
        probs = jnp.pad(probs, ((0, 0), (0, pad_p)))
        sorted_probs = jnp.pad(sorted_probs, ((0, 0), (0, pad_p)))
        cov_desc = jnp.pad(cov_desc, ((0, 0), (0, pad_p)))
        if has_write:
            wprobs = jnp.pad(wprobs, ((0, 0), (0, pad_p)))
            if wprobs_q is not None:
                wprobs_q = jnp.pad(wprobs_q, ((0, 0), (0, pad_p)))
    if pad_c:
        caps_f = jnp.pad(caps_f, ((0, 0), (0, pad_c)),
                         constant_values=-1.0)
        caps_i = jnp.pad(caps_i, ((0, 0), (0, pad_c)), constant_values=-1)
        ids = jnp.pad(ids, ((0, 0), (0, pad_c)), constant_values=PAD_ID)
    pp, cc = p_width + pad_p, c + pad_c

    def row(x, width):
        # (K, X) -> (K, 1, X) with the row axis squeezed out of the block:
        # each program sees a (1, X) tile whose dims equal the array's own,
        # which Mosaic accepts for any K
        spec = pl.BlockSpec((None, 1, width), lambda i: (i, 0, 0))
        return x.reshape(k, 1, width), spec

    lanes = [(probs, pp)]
    if policy in ("lfu", "multi"):
        lanes.append((sorted_probs, pp))
    if has_sorted and policy in ("lfu", "multi"):
        lanes.append((cov_desc, pp))
    if has_write:
        lanes.append((wprobs, pp))
        if policy in ("lfu", "multi"):
            lanes.append((wprobs_q, pp))
    lanes += [(f32s, _F32_COLS), (i32s, _I32_COLS), (caps_f, cc),
              (caps_i, cc), (ids, cc)]
    inputs, in_specs = zip(*(row(x, wd) for x, wd in lanes))

    # the argmin accumulator is two scalars revisited by every program
    acc_spec = pl.BlockSpec((1, 1), lambda i: (0, 0),
                            memory_space=pltpu.SMEM)
    h, best_val, best_id = pl.pallas_call(
        functools.partial(_price_kernel, policy=policy,
                          has_sorted=has_sorted, has_write=has_write,
                          iters=iters, n_in=len(inputs)),
        grid=(k,),
        in_specs=list(in_specs),
        out_specs=[
            pl.BlockSpec((None, 1, cc), lambda i: (i, 0, 0)),
            acc_spec,
            acc_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, 1, cc), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="price_grid",
    )(*inputs)
    return h[:, 0, :c], best_val, best_id
