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

The histograms stay on the device, but the solve reads back to the host,
each read a ``host_sync`` (``repro.obs``):

* before the launch, each row's distinct pages ``nd_i`` and least
  non-zero probability ``pmin``, which the kernel takes as scalars;
* after it, the hit rates ``h2`` and the argmin ``best_id``, and, for a
  table with sorted parts, the distinct pages ``nd_row`` of the mixed
  histogram.

``price.marshal`` spans the host work up to and including the launch,
``price.wait`` the first read after it.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.session import SortedScanPart, _compulsory_coverage
from repro.kernels import ops as kernel_ops
from repro.kernels import price_grid as _pg

__all__ = ["DeviceExecutor"]

_CAP_MAX = 2**31 - 129   # matches core.session._exact_cap_array


def _exact_i32(values) -> np.ndarray:
    arr = np.floor(np.asarray(values, np.float64))
    return np.clip(arr, -1, _CAP_MAX).astype(np.int32)


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
            caps_f = caps_i.astype(np.float32)

            # ---- per-row statistics (solve_profiles preprocessing) ----------
            counts = profiles.counts[jnp.asarray(urows)]            # (K, P)
            num_pages = int(profiles.counts.shape[1])
            sample_f = np.asarray(profiles.totals, np.float64)[urows]
            sample_f = sample_f.astype(np.float32)
            full_f = sample_f * np.float32(profiles.scale)
            wps = ([profiles.wparts[i] for i in urows]
                   if profiles.wparts else [])
            has_write = any(wp is not None for wp in wps)
            if has_write:
                # fold the write stream into the request histogram BEFORE
                # normalizing (hit_rate_grid order): writes fault their pages
                # like reads, and probs/n_distinct/pmin describe the mix.
                zero_w = jnp.zeros((num_pages,), jnp.float32)
                w_counts = jnp.stack(
                    [jnp.asarray(wp.counts, jnp.float32) if wp is not None
                     else zero_w for wp in wps])
                w_refs = np.asarray([wp.total_refs if wp is not None else 0.0
                                     for wp in wps], np.float32)
                counts = counts + w_counts
                sample_f = sample_f + w_refs
                full_f = full_f + w_refs * np.float32(profiles.scale)
            probs = counts / jnp.maximum(
                jnp.asarray(sample_f)[:, None], 1e-30)
            nd_i = obs.to_host(jnp.sum(counts > 0, axis=1), np.int64)
            pmin = obs.to_host(jnp.maximum(
                jnp.min(jnp.where(probs > 0, probs, jnp.inf), axis=1), 1e-30),
                np.float32)
            scale = np.asarray(row_scale, np.float64)[urows].astype(np.float32)

            sparts = [profiles.sparts[i] for i in urows]
            has_sorted = any(sp is not None for sp in sparts)
            surrogate = {}
            f32s = np.zeros((k, _pg._F32_COLS), np.float32)
            i32s = np.zeros((k, _pg._I32_COLS), np.int32)
            f32s[:, 0], f32s[:, 1] = sample_f, full_f
            f32s[:, 2] = nd_i.astype(np.float32)
            f32s[:, 3], f32s[:, 8] = pmin, scale
            i32s[:, 0] = _exact_i32(nd_i)
            i32s[:, 3] = upols                  # read iff policy == "multi"

            dummy = jnp.zeros((k, 1), jnp.float32)
            cov = cov_desc = dummy
            if has_sorted:
                zero = SortedScanPart(
                    0.0, 0.0, 1, jnp.zeros((num_pages,), jnp.float32), 0.0)
                sps = [sp if sp is not None else zero for sp in sparts]
                for i, sp in enumerate(sps):
                    if sp.coverage is None:
                        surrogate[i] = sp.distinct_pages
                        sps[i] = dataclasses.replace(
                            sp, coverage=_compulsory_coverage(sp, num_pages))
                f32s[:, 4] = [sp.total_refs for sp in sps]
                f32s[:, 5] = f32s[:, 4] * np.float32(profiles.scale)
                i32s[:, 1] = _exact_i32([sp.distinct_pages for sp in sps])
                f32s[:, 6] = i32s[:, 1].astype(np.float32)
                f32s[:, 7] = [sp.pinned_retouches for sp in sps]
                i32s[:, 2] = _exact_i32([sp.min_capacity for sp in sps])
                cov = jnp.stack([jnp.asarray(sp.coverage, jnp.float32)
                                 for sp in sps])
                if policy in ("lfu", "multi"):
                    cov_desc = -jnp.sort(-cov, axis=1)
            sorted_probs = (-jnp.sort(-probs, axis=1)
                            if policy in ("lfu", "multi") else dummy)
            wprobs = wprobs_q = None
            if has_write:
                wprobs = w_counts / jnp.maximum(
                    jnp.asarray(sample_f)[:, None], 1e-30)
                if policy in ("lfu", "multi"):
                    # the LFU resident set is the top-C of the COMBINED stream;
                    # permute write mass into that order (argsort tie-break
                    # matches cache_models._writeback_terms)
                    wprobs_q = jnp.take_along_axis(
                        wprobs, jnp.argsort(-probs, axis=1), axis=1)

            # ---- one fused launch -------------------------------------------
            h2, _, best_id = _pg.price_grid(
                policy, probs, sorted_probs, cov_desc,
                jnp.asarray(f32s), jnp.asarray(i32s), jnp.asarray(caps_f),
                jnp.asarray(caps_i), jnp.asarray(ids), wprobs, wprobs_q,
                has_sorted=has_sorted, has_write=has_write,
                interpret=kernel_ops._auto_interpret(self.interpret))
        with obs.span("price.wait"):
            h = obs.to_host(h2, np.float64)[inv, slot]

        # ---- distinct pages (host-side closed forms, as solve_profiles) -
        if has_sorted:
            nd_row = obs.to_host(
                jnp.sum((counts > 0) | (cov > 0), axis=1), np.float64)
            for i, true_n in surrogate.items():
                nd_row[i] = float(nd_i[i]) + true_n
        else:
            nd_row = nd_i.astype(np.float64)

        best = int(obs.to_host(best_id)[0, 0])
        return h, nd_row[inv], (best if best < _pg.PAD_ID else None)
