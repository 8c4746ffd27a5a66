"""Pallas TPU multi-candidate Che-consistency evaluator (the CAM hot loop).

The paper's tuner solves  C = sum_i (1 - exp(-p_i * T))  once per
(eps, memory-budget, policy) candidate — a memory-bound reduction over the
page-popularity array repeated ~64x by scalar bisection.  TPU adaptation:
evaluate K candidate characteristic times per HBM pass (the p_i block is
loaded into VMEM once and reused for all K exponentials), turning K-1 of
every K passes into pure VPU work.  An interval-subdivision search with K=8
needs ~20 passes for f32 precision vs 64 for scalar bisection — a ~3.2x HBM
traffic reduction on the dominant term.

Grid: (N/block_n,) over the (N/128, 128)-reshaped popularity array; the (1,K)
output tile is revisited by every program ("arbitrary" semantics) and
accumulated in place.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["che_sums", "che_solve"]

_LANES = 128


def _kernel(p_ref, t_ref, o_ref, *, k: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    p = p_ref[...].astype(jnp.float32)                     # (rows, 128)
    t = t_ref[...].astype(jnp.float32)                     # (1, K)
    # (rows, 128, K): one exp per (page, candidate); padded pages have p=0
    # and contribute exactly 0 via expm1.
    contrib = -jnp.expm1(-p[..., None] * t[0][None, None, :])
    o_ref[...] += jnp.sum(contrib, axis=(0, 1))[None, :]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def che_sums(probs, t_candidates, *, block_rows: int = 256,
             interpret: bool = False):
    """sum_i (1 - exp(-p_i * t_k)) for each of K candidates, one HBM pass.

    probs: (N,) float32; t_candidates: (K,). Returns (K,) float32.
    """
    n = probs.shape[0]
    k = t_candidates.shape[0]
    rows = -(-n // _LANES)
    pad = rows * _LANES - n
    p2 = jnp.pad(probs.astype(jnp.float32), (0, pad)).reshape(rows, _LANES)
    row_pad = (-rows) % block_rows
    if row_pad:
        p2 = jnp.pad(p2, ((0, row_pad), (0, 0)))
    t2 = t_candidates.astype(jnp.float32).reshape(1, k)
    grid = ((rows + row_pad) // block_rows,)

    out = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, k), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, k), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(p2, t2)
    return out[0]


@functools.partial(jax.jit, static_argnames=("k", "iters", "interpret"))
def che_solve(probs, capacity, *, k: int = 8, iters: int = 20,
              interpret: bool = False):
    """Solve C = sum_i (1 - exp(-p_i T)) by K-way interval subdivision.

    Each iteration shrinks the bracket by (K+1)x with ONE pass over probs.
    """
    probs = probs.astype(jnp.float32)
    capacity = jnp.asarray(capacity, jnp.float32)
    pmin = jnp.maximum(jnp.min(jnp.where(probs > 0, probs, jnp.inf)), 1e-30)
    hi0 = jnp.maximum(4.0 * capacity / pmin, 1.0)
    # The bracket can span 20+ orders of magnitude (pmin is tiny for zipf
    # popularity), so subdivide in LOG space: each pass cuts the log-range
    # by (K+1)x, converging in ~5 passes where linear subdivision needs 40+.
    lo0 = hi0 * jnp.float32(1e-30)

    def body(_, bracket):
        log_lo, log_hi = bracket
        fracs = jnp.arange(1, k + 1, dtype=jnp.float32) / (k + 1)
        log_ts = log_lo + (log_hi - log_lo) * fracs
        sums = che_sums(probs, jnp.exp(log_ts), interpret=interpret)
        below = sums < capacity                    # monotone increasing in T
        # rightmost candidate still below C bounds the solution from the left
        idx = jnp.sum(below.astype(jnp.int32))     # in [0, K]
        grid_pts = jnp.concatenate([log_lo[None], log_ts, log_hi[None]])
        return grid_pts[idx], grid_pts[idx + 1]

    log_lo, log_hi = jax.lax.fori_loop(
        0, iters, body, (jnp.log(lo0), jnp.log(hi0)))
    return jnp.exp(0.5 * (log_lo + log_hi))
