"""DeviceExecutor — the fused PriceTable executor.

Marshals a PriceTable into the padded (row x cell-slot) layout of
``kernels/price_grid.py`` and solves the whole table in one pallas
launch: the policy fixed point, the sorted/mixed composition and the
objective argmin fuse into a single kernel (interpret mode off-TPU, via
the same auto rule as the other kernels).  Preprocessing mirrors
``CostSession.solve_profiles`` exactly — zero-part substitution for
sorted composition, the compulsory-equivalent coverage surrogate for
legacy coverage-less parts, exact int32 capacity clamps — so results are
float32-equivalent to the HostExecutor (pinned by tests/test_engine.py).

The row statistics (the probabilities, their descending sorts, each
row's distinct pages ``nd_i`` and least non-zero probability ``pmin``)
are one compiled call, :func:`_row_stats`, that writes ``nd_i`` and
``pmin`` into the kernel's scalar columns on the device, so a solve
dispatches twice and waits on the device once.  After the launch the
solve reads back to the host, each read a ``host_sync`` (``repro.obs``):
the hit rates ``h2``, ``nd_i``, the argmin ``best_id``, and, for a table
with sorted parts, the distinct pages ``nd_row`` of the mixed histogram.

``price.marshal`` spans the host work up to and including the launch,
``price.wait`` the first read after it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.backend import default_interpret
from repro.core.session import SortedScanPart, _compulsory_coverage
from repro.kernels import price_grid as _pg

__all__ = ["DeviceExecutor"]

_CAP_MAX = 2**31 - 129   # matches core.session._exact_cap_array


def _exact_i32(values) -> np.ndarray:
    arr = np.floor(np.asarray(values, np.float64))
    return np.clip(arr, -1, _CAP_MAX).astype(np.int32)


def _stack_rows(rows, num_pages: int) -> jnp.ndarray:
    """(K, P) float32 from per-row (P,) arrays; None rows are zero."""
    zero = jnp.zeros((num_pages,), jnp.float32)
    return jnp.stack([zero if r is None else jnp.asarray(r, jnp.float32)
                      for r in rows])


@functools.partial(jax.jit, static_argnames=("policy", "has_sorted",
                                             "has_write"))
def _row_stats(policy, counts_all, urows, w_rows, cov_rows, f32s, i32s,
               caps_i, *, has_sorted, has_write):
    """The kernel's inputs for rows ``urows`` of ``counts_all``, in one
    compiled call.

    ``f32s`` / ``i32s`` come with every column but ``nd_i`` and ``pmin``
    filled (column 0 of ``f32s`` is each row's request mass); this fills
    those.  Returns the kernel's arguments ``(probs, sorted_probs,
    cov_desc, f32s, i32s, caps_f, caps_i, wprobs, wprobs_q)`` and ``(nd_i,
    nd_row)``; ``nd_row`` is None without sorted parts.
    """
    num_pages = counts_all.shape[1]
    counts = counts_all[urows]                                  # (K, P)
    lfu = policy in ("lfu", "multi")
    if has_write:
        # fold the write stream into the request histogram BEFORE
        # normalizing (hit_rate_grid order): writes fault their pages like
        # reads, and probs/n_distinct/pmin describe the mix.
        w_counts = _stack_rows(w_rows, num_pages)
        counts = counts + w_counts
    norm = jnp.maximum(f32s[:, :1], 1e-30)
    probs = counts / norm
    nd_i = jnp.sum(counts > 0, axis=1)
    pmin = jnp.maximum(
        jnp.min(jnp.where(probs > 0, probs, jnp.inf), axis=1), 1e-30)
    f32s = f32s.at[:, 2].set(nd_i.astype(jnp.float32)).at[:, 3].set(pmin)
    i32s = i32s.at[:, 0].set(nd_i.astype(jnp.int32))

    dummy = jnp.zeros((counts.shape[0], 1), jnp.float32)
    cov = cov_desc = dummy
    nd_row = None
    if has_sorted:
        cov = _stack_rows(cov_rows, num_pages)
        if lfu:
            cov_desc = -jnp.sort(-cov, axis=1)
        nd_row = jnp.sum((counts > 0) | (cov > 0), axis=1)
    sorted_probs = -jnp.sort(-probs, axis=1) if lfu else dummy
    wprobs = wprobs_q = None
    if has_write:
        wprobs = w_counts / norm
        if lfu:
            # the LFU resident set is the top-C of the COMBINED stream;
            # permute write mass into that order (argsort tie-break
            # matches cache_models._writeback_terms)
            wprobs_q = jnp.take_along_axis(
                wprobs, jnp.argsort(-probs, axis=1), axis=1)
    return ((probs, sorted_probs, cov_desc, f32s, i32s,
             caps_i.astype(jnp.float32), caps_i, wprobs, wprobs_q),
            (nd_i, nd_row))


class DeviceExecutor:
    """Solve a PriceTable through the fused price-grid kernel."""

    name = "device"

    def __init__(self, interpret: Optional[bool] = None):
        self.interpret = interpret

    def solve(self, engine, table, row_scale):
        from repro.core.cache_models import POLICIES
        with obs.span("price.marshal"):
            profiles = table.profiles
            rows = np.asarray(table.rows, np.int64)
            t = rows.shape[0]

            # ---- per-cell policies: group by (profile row, policy) ----------
            # A kernel program owns ONE fixed point, so multi-policy tables
            # split a profile row into one program per policy it prices under;
            # single-policy tables reduce to the plain per-row grouping.
            base_code = POLICIES.index(engine.cost.system.policy)
            if table.pols is None:
                cell_pols = np.full(t, base_code, np.int64)
            else:
                cell_pols = np.asarray(table.pols, np.int64)
                cell_pols = np.where(cell_pols < 0, base_code, cell_pols)
            ukeys, inv = np.unique(rows * len(POLICIES) + cell_pols,
                                   return_inverse=True)
            urows = ukeys // len(POLICIES)
            upols = (ukeys % len(POLICIES)).astype(np.int32)
            k = urows.shape[0]
            upol_set = set(upols.tolist())
            policy = (POLICIES[upol_set.pop()] if len(upol_set) == 1
                      else "multi")

            # ---- cell layout: group cells by profile row, keep table order --
            per_row = np.bincount(inv, minlength=k)
            c_max = int(per_row.max())
            order = np.argsort(inv, kind="stable")
            starts = np.zeros(k, np.int64)
            starts[1:] = np.cumsum(per_row)[:-1]
            slot = np.empty(t, np.int64)
            slot[order] = np.arange(t) - starts[inv[order]]

            caps_i = np.full((k, c_max), -1, np.int32)
            ids = np.full((k, c_max), _pg.PAD_ID, np.int32)
            caps_i[inv, slot] = _exact_i32(table.caps)
            ids[inv, slot] = np.arange(t, dtype=np.int32)

            # ---- per-row statistics (solve_profiles preprocessing) ----------
            num_pages = int(profiles.counts.shape[1])
            sample_f = np.asarray(profiles.totals, np.float64)[urows]
            sample_f = sample_f.astype(np.float32)
            full_f = sample_f * np.float32(profiles.scale)
            wps = ([profiles.wparts[i] for i in urows]
                   if profiles.wparts else [])
            has_write = any(wp is not None for wp in wps)
            w_rows = None
            if has_write:
                w_rows = tuple(None if wp is None else wp.counts
                               for wp in wps)
                w_refs = np.asarray([wp.total_refs if wp is not None else 0.0
                                     for wp in wps], np.float32)
                sample_f = sample_f + w_refs
                full_f = full_f + w_refs * np.float32(profiles.scale)
            scale = np.asarray(row_scale, np.float64)[urows].astype(np.float32)

            sparts = [profiles.sparts[i] for i in urows]
            has_sorted = any(sp is not None for sp in sparts)
            surrogate = {}
            f32s = np.zeros((k, _pg._F32_COLS), np.float32)
            i32s = np.zeros((k, _pg._I32_COLS), np.int32)
            f32s[:, 0], f32s[:, 1] = sample_f, full_f
            f32s[:, 8] = scale                  # columns 2, 3: _row_stats
            i32s[:, 3] = upols                  # read iff policy == "multi"

            cov_rows = None
            if has_sorted:
                zero = SortedScanPart(0.0, 0.0, 1, None, 0.0)
                sps = [sp if sp is not None else zero for sp in sparts]
                for i, sp in enumerate(sps):
                    if sp.coverage is None and sp is not zero:
                        surrogate[i] = sp.distinct_pages
                        sps[i] = dataclasses.replace(
                            sp, coverage=_compulsory_coverage(sp, num_pages))
                f32s[:, 4] = [sp.total_refs for sp in sps]
                f32s[:, 5] = f32s[:, 4] * np.float32(profiles.scale)
                i32s[:, 1] = _exact_i32([sp.distinct_pages for sp in sps])
                f32s[:, 6] = i32s[:, 1].astype(np.float32)
                f32s[:, 7] = [sp.pinned_retouches for sp in sps]
                i32s[:, 2] = _exact_i32([sp.min_capacity for sp in sps])
                cov_rows = tuple(sp.coverage for sp in sps)

            kernel_args, (nd_dev, nd_row_dev) = _row_stats(
                policy, profiles.counts, urows.astype(np.int32), w_rows,
                cov_rows, f32s, i32s, caps_i, has_sorted=has_sorted,
                has_write=has_write)
            (probs, sorted_probs, cov_desc, f32s, i32s, caps_f, caps_i,
             wprobs, wprobs_q) = kernel_args

            # ---- one fused launch -------------------------------------------
            h2, _, best_id = _pg.price_grid(
                policy, probs, sorted_probs, cov_desc, f32s, i32s, caps_f,
                caps_i, ids, wprobs, wprobs_q, has_sorted=has_sorted,
                has_write=has_write,
                interpret=default_interpret(self.interpret))
            for out in (h2, nd_dev, best_id, nd_row_dev):
                if out is not None:     # every read below rides one wait
                    out.copy_to_host_async()
        with obs.span("price.wait"):
            h = obs.to_host(h2, np.float64)[inv, slot]
        nd_i = obs.to_host(nd_dev, np.int64)

        # ---- distinct pages (host-side closed forms, as solve_profiles) -
        if has_sorted:
            nd_row = obs.to_host(nd_row_dev, np.float64)
            for i, true_n in surrogate.items():
                nd_row[i] = float(nd_i[i]) + true_n
        else:
            nd_row = nd_i.astype(np.float64)

        best = int(obs.to_host(best_id)[0, 0])
        return h, nd_row[inv], (best if best < _pg.PAD_ID else None)
