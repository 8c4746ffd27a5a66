"""TPU-native mixed-eps occupancy: the device half of the profiling side.

``core/page_ref.py::point_page_refs_mixed_eps_grid`` — the §V-C grouped
mixture-histogram kernel behind every RMI branch-grid profile — is
deliberately host-side: one LUT-row gather plus one weighted
``np.bincount`` per eps class, which beats XLA CPU scatters ~10x but caps
tuning-loop and drift-retune scale exactly where the ROADMAP's
"device-resident tuning fabric, leg 2" says it does.  This module is the
TPU-native counterpart: per-eps-class page occupancy as banded ONE-HOT
MATMULS over device-resident position arrays, so the histograms are born
in HBM and can chain straight into the fused pricing kernel
(``kernels/price_grid.py``) without ever visiting the host.

The factorization replaces both host gathers with MXU contractions.  With
queries grouped by pow2 leaf-eps class exactly like the host path
(``page_ref.mixed_eps_class_codes`` — the SAME helper), stack every
class's Eq. 12 LUT, centered on the grid-wide max radius D, into one

    lutstack[d, c * C_ipp + s] = LUT_c[s, d - (D - D_c)]      (W, n_c*C_ipp)

and encode each query as the combined key ``code * C_ipp + slot``.  Then
for one candidate row, one page tile (columns ``[j0, j0 + PT)``) and one
query tile:

    SEL[cs, q] = [key_q == cs]              one-hot     (n_c*C_ipp, QT)
    T1         = lutstack @ SEL             banded mass (W, QT)
    OH[q, c]   = [page_q == j0 - L + c]     one-hot     (QT, PT + L)
    M          = T1 @ OH                                (W, PT + L)
    counts[j0 + j] += sum_d M[d, j + L - d]             j in [0, PT)

since ``counts[page_q + d] += T1[d, q]`` for d in [0, W) is
``sum_d M[d, j - d]`` with M's columns offset by the margin L.  L is
``W - 1`` rounded up to the 128-lane width, which keeps each band row's
turn within one vreg for Mosaic's strided ``pltpu.roll``: one roll per
128 band rows lines the diagonals up, and a sublane sum adds them.  One
one-hot and three MXU passes per program, whatever W; no gathers, no
scatters, only iota compares, MXU work and lane rotations.  Both
contractions split their f32 operand into three bf16 parts against a
0/1 one-hot (:func:`_dot_onehot`), so every product is exact and only the
f32 summation order differs from the host.  Padded queries carry key -1
and never match.

The output is the SAME padded ``(K, P + 2D)`` layout the host kernel
accumulates into (out-of-range window mass lands in the pad and is
sliced off); :func:`point_page_refs_mixed_eps_grid` mirrors the host
function's signature and slicing exactly.  Equivalence: exact for
integer-mass inputs (every LUT entry 0 or 1 — f32 sums of integers), and
float32-tolerance otherwise; pinned host-vs-device by
tests/test_kernels.py across families x policies x workloads and band
widths of 3 to 65 pages over several page tiles.

Grid = (K rows, page tiles, query tiles); each program owns one
candidate row x one page-tile block of the padded histogram and
accumulates its query tiles into the revisited block (zero-initialized on
the first visit), so VMEM stays bounded whatever the workload size.
Interpret mode off-TPU via the shared ``kernels.ops._auto_interpret``
rule.  On a TPU v5e it compiles (tests/test_tpu_compile.py) at every P
tried, up to 4M pages: a program's VMEM depends on the tiles and the
stacked LUT width (the SEL one-hot), and on W only through the
(W, PT + L) block M, so the 65-page bands of RMI branches 64..512 over
8M books keys (13 eps classes) compile within v5e's 16 MiB of scoped
VMEM with no ``vmem_limit_bytes`` set.

Host work per call, each step a program span (``repro.obs``):
``profile.prep`` (class codes, dense rank, the LUT stack, the transfers
and the launch) and ``profile.wait`` (the first host read, the totals).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.core import page_ref
from repro.kernels import ops as kernel_ops

__all__ = ["profile_grid", "point_page_refs_mixed_eps_grid"]

_LANES = 128
_SUBLANES = 8
# A wider page tile recomputes T1 for fewer tiles; a narrower query tile
# keeps the wider one-hot within scoped VMEM.  Against 512 x 2048 on a
# v5e, 256 x 8192 halves the kernel's time and compiles for every band
# width and class count the narrower tile did.
_Q_TILE = 256        # queries resident per program
_P_TILE = 8192       # padded-histogram columns per program


def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


def _dot_onehot(x, onehot):
    """``x @ onehot`` to f32 rounding, from the MXU's bf16 passes.

    A TPU contracts f32 operands in one bf16 pass unless told otherwise,
    which would round the Eq. 12 fractions to 8 bits (precision=HIGHEST
    runs out of VMEM at real LUT widths).  ``onehot`` holds 0/1, exact in
    bf16, so three bf16 parts of ``x`` (which sum to ``x`` exactly) give
    exact products; only the f32 accumulation rounds.
    """
    out = None
    for _ in range(3):
        part = x.astype(jnp.bfloat16)
        x = x - part.astype(jnp.float32)
        prod = jnp.dot(part, onehot, preferred_element_type=jnp.float32)
        out = prod if out is None else out + prod
    return out


def _occupancy_kernel(keys_ref, pages_ref, lut_ref, out_ref, *,
                      n_cc: int, q_tile: int, p_tile: int, margin: int):
    """One program = one candidate row x one page tile x one query tile."""
    pt_i = pl.program_id(1)
    qt_i = pl.program_id(2)

    @pl.when(qt_i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    keys = keys_ref[...]                                    # (1, QT) int32
    pages = pages_ref[...]                                  # (1, QT) int32
    lut = lut_ref[...]                                      # (Wp, CCp) f32

    # one-hot over the combined (class, slot) key; pad queries (key -1)
    # match nothing, so their T1 column is zero and they contribute nothing
    sel = (jax.lax.broadcasted_iota(jnp.int32, (n_cc, q_tile), 0)
           == keys).astype(jnp.bfloat16)
    t1 = _dot_onehot(lut, sel)                              # (Wp, QT)

    # one one-hot over the page tile and the lane-aligned left margin:
    # column c is global column pt_i*PT - margin + c
    oh = (jax.lax.broadcasted_iota(jnp.int32, (q_tile, p_tile + margin), 1)
          + (pt_i * p_tile - margin) == pages.T).astype(jnp.bfloat16)
    m = _dot_onehot(t1, oh)                                 # (Wp, PT+margin)
    # band row d sits margin-d columns right of its output column: a
    # strided roll turns row d left by that much (right by ext-margin+d).
    # Mosaic keeps a strided roll's spread of turns within one vreg, hence
    # one roll per 128 band rows, each based on a whole number of vregs.
    # The first PT columns never wrap; rows past the band are zero.
    ext = p_tile + margin
    acc = out_ref[...]
    for r0 in range(0, m.shape[0], _LANES):
        band = pltpu.roll(m[r0:r0 + _LANES], (ext - margin + r0) % ext, 1,
                          stride=1, stride_axis=0)
        acc = acc + jnp.sum(band, axis=0, keepdims=True)[:, :p_tile]
    out_ref[...] = acc


@functools.partial(jax.jit,
                   static_argnames=("width", "pad", "interpret"))
def profile_grid(keys, pages, lutstack, *, width: int, pad: int,
                 interpret: bool = False) -> jnp.ndarray:
    """Banded one-hot occupancy of a whole candidate grid in one launch.

    Args:
      keys: (K, Q) int32 combined ``class_code * C_ipp + slot`` per query
        (per candidate row); padded queries carry -1.
      pages: (1, Q) int32 shared query pages (any value where keys == -1).
      lutstack: (W', CC') float32 stacked per-class LUTs, centered on the
        grid-wide max radius (layout in the module docstring); W' / CC'
        may carry zero padding to sublane / lane multiples.
      width: the true band width ``2 * max_radius + 1`` (<= W').
      pad: the true padded histogram width ``num_pages + 2 * max_radius``.

    Returns:
      (K, pad) float32 — the SAME padded layout the host kernel
      accumulates into; callers slice ``[:, D : D + num_pages]``.
    """
    k, q = keys.shape
    q_tile = min(_Q_TILE, _ceil_to(q, _LANES))
    qp = _ceil_to(q, q_tile)
    p_tile = min(_P_TILE, _ceil_to(pad, _LANES))
    pp = _ceil_to(pad, p_tile)
    if qp > q:
        keys = jnp.pad(keys, ((0, 0), (0, qp - q)), constant_values=-1)
        pages = jnp.pad(pages, ((0, 0), (0, qp - q)), constant_values=-1)
    n_cc = int(lutstack.shape[1])
    margin = _ceil_to(width - 1, _LANES)

    # rows ride a squeezed leading axis: each program sees (1, tile)
    # blocks, which Mosaic accepts for any K
    out = pl.pallas_call(
        functools.partial(_occupancy_kernel, n_cc=n_cc, q_tile=q_tile,
                          p_tile=p_tile, margin=margin),
        grid=(k, pp // p_tile, qp // q_tile),
        in_specs=[
            pl.BlockSpec((None, 1, q_tile), lambda i, p, t: (i, 0, t)),
            pl.BlockSpec((1, q_tile), lambda i, p, t: (0, t)),
            pl.BlockSpec(lutstack.shape, lambda i, p, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, p_tile),
                               lambda i, p, t: (i, 0, p)),
        out_shape=jax.ShapeDtypeStruct((k, 1, pp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="profile_grid",
    )(keys.reshape(k, 1, qp), pages, lutstack)
    return out[:, 0, :pad]


def _lut_stack(class_eps, c_ipp: int, max_radius: int) -> np.ndarray:
    """Stack per-class Eq. 12 LUTs centered on the grid-wide max radius.

    Centering reproduces the host kernel's ``base + (D - D_c) + d'``
    offset arithmetic: class c's width-``2*D_c+1`` band sits at rows
    ``[D - D_c, D + D_c]`` of the shared width-``2*D+1`` band, and all
    other rows are zero — so one uniform ``page + d`` target rule serves
    every class.
    """
    width = 2 * max_radius + 1
    wp = _ceil_to(width, _SUBLANES)
    ccp = _ceil_to(len(class_eps) * c_ipp, _LANES)
    stack = np.zeros((wp, ccp), np.float32)
    for ci, eps in enumerate(class_eps):
        radius = page_ref.lut_radius(eps, c_ipp)
        lut = page_ref._point_lut_np(eps, c_ipp)       # (C_ipp, 2*D_c+1)
        off = max_radius - radius
        stack[off:off + 2 * radius + 1,
              ci * c_ipp:(ci + 1) * c_ipp] = lut.T.astype(np.float32)
    return stack


def point_page_refs_mixed_eps_grid(
    positions: np.ndarray,
    eps_rows: np.ndarray,
    c_ipp: int,
    num_pages: int,
    *,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, np.ndarray]:
    """Device counterpart of ``page_ref.point_page_refs_mixed_eps_grid``.

    Same signature, same grouping (one shared class-code pass through
    ``page_ref.mixed_eps_class_codes``), same padded-accumulate-then-slice
    semantics — but the histograms are computed on-device and RETURNED as a
    device array, so a caller chaining into the fused pricing kernel never
    round-trips them through the host.

    Returns (counts (K, num_pages) float32 device array, totals (K,)
    float64 host array) — shapes and meaning identical to the host kernel.
    """
    with obs.span("profile.prep"):
        positions = np.asarray(positions, np.int64)
        eps_rows = np.maximum(np.asarray(eps_rows, np.int64), 1)
        k, q_n = eps_rows.shape
        if positions.shape[0] != q_n:
            raise ValueError(f"eps_rows has {q_n} columns for "
                             f"{positions.shape[0]} positions")
        page = (positions // c_ipp).astype(np.int32)
        slot = (positions - page.astype(np.int64) * c_ipp).astype(np.int32)
        max_radius = page_ref.lut_radius(int(eps_rows.max()), c_ipp)
        pad = num_pages + 2 * max_radius

        codes, classes = page_ref.mixed_eps_class_codes(eps_rows.ravel())
        present = np.flatnonzero(np.bincount(codes))
        class_eps = [page_ref.mixed_eps_class_eps(c, classes)
                     for c in present]
        # dense-rank the (possibly sparse) codes into lutstack column groups
        dense = np.searchsorted(present,
                                codes.astype(np.int64)).astype(np.int32)
        keys = dense.reshape(k, q_n) * np.int32(c_ipp) + slot[None, :]

        padded = profile_grid(
            jnp.asarray(keys), jnp.asarray(page[None, :]),
            jnp.asarray(_lut_stack(class_eps, c_ipp, max_radius)),
            width=2 * max_radius + 1, pad=pad,
            interpret=kernel_ops._auto_interpret(interpret))
        counts = padded[:, max_radius:max_radius + num_pages]
        totals = jnp.sum(counts, axis=1)
    with obs.span("profile.wait"):
        totals = obs.to_host(totals, np.float64)
    return counts, totals
