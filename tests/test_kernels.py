"""Pallas kernel validation: the occupancy kernel against the host bincount
oracle (interpret mode off-TPU: the kernel body executes on CPU)."""
import numpy as np
import pytest

from repro.core import page_ref
from repro.kernels import profile_grid

C_IPP = 128


def _occupancy_pair(positions, eps_rows, num_pages):
    ch, th = page_ref.point_page_refs_mixed_eps_grid(
        positions, eps_rows, C_IPP, num_pages)
    cd, td = profile_grid.point_page_refs_mixed_eps_grid(
        positions, eps_rows, C_IPP, num_pages)
    assert np.asarray(ch).shape == np.asarray(cd).shape
    return (np.asarray(ch, np.float64), np.asarray(th, np.float64),
            np.asarray(cd, np.float64), np.asarray(td, np.float64))


def test_occupancy_exact_for_integer_mass():
    """Slots >= 2*eps from both page boundaries make every Eq. 12 LUT entry
    exactly 0 or 1, so the device float32 sums must carry the integer mass
    EXACTLY — bit-equal counts and totals, no tolerance."""
    rng = np.random.default_rng(11)
    num_pages, q = 40, 1500
    positions = rng.integers(0, num_pages, q) * C_IPP \
        + rng.integers(16, 112, q)
    eps_rows = rng.choice([1, 2, 4], size=(3, q)).astype(np.int64)
    ch, th, cd, td = _occupancy_pair(positions, eps_rows, num_pages)
    assert np.all(ch == np.round(ch))            # really integer mass
    assert np.array_equal(ch, cd)
    assert np.array_equal(th, td)


def test_occupancy_general_within_float32_tolerance():
    """Arbitrary slots + large pow2 eps classes: fractional LUT mass, so
    host float64 and device float32 accumulation differ only by summation
    order — <= 2e-6 normalized."""
    rng = np.random.default_rng(5)
    num_pages, q = 64, 4000
    positions = rng.integers(0, num_pages * C_IPP, q)
    eps_rows = rng.choice([1, 4, 16, 64, 256], size=(4, q)).astype(np.int64)
    ch, th, cd, td = _occupancy_pair(positions, eps_rows, num_pages)
    scale = max(1.0, float(ch.max()))
    assert np.max(np.abs(ch - cd)) / scale < 2e-6
    assert np.max(np.abs(th - td) / np.maximum(th, 1.0)) < 2e-6


def test_occupancy_non_pow2_eps_fallback():
    """Non-pow2 eps rows exercise the unique-rank class coding (no popcount
    shortcut); both kernels share mixed_eps_class_codes so class grouping
    is identical and the results agree."""
    rng = np.random.default_rng(9)
    num_pages, q = 32, 900
    positions = rng.integers(0, num_pages * C_IPP, q)
    eps_rows = rng.choice([3, 5, 12, 100], size=(2, q)).astype(np.int64)
    ch, th, cd, td = _occupancy_pair(positions, eps_rows, num_pages)
    scale = max(1.0, float(ch.max()))
    assert np.max(np.abs(ch - cd)) / scale < 2e-6


def test_occupancy_eps_zero_clamped_to_one():
    """eps=0 rows clamp to eps=1 on both sides (the host kernel's guard)."""
    rng = np.random.default_rng(2)
    num_pages, q = 16, 400
    positions = rng.integers(0, num_pages * C_IPP, q)
    zeros = np.zeros((1, q), np.int64)
    ones = np.ones((1, q), np.int64)
    _, _, cd0, td0 = _occupancy_pair(positions, zeros, num_pages)
    _, _, cd1, td1 = _occupancy_pair(positions, ones, num_pages)
    assert np.array_equal(cd0, cd1)
    assert np.array_equal(td0, td1)


@pytest.mark.parametrize("q,num_pages", [(100, 7), (777, 37), (513, 129)])
def test_occupancy_ragged_shapes(q, num_pages):
    """Query counts off the 512-query tile and page counts off the lane
    width pad internally; padded queries (key -1) contribute nothing and
    the output slices back to exactly (K, num_pages)."""
    rng = np.random.default_rng(q)
    positions = rng.integers(0, num_pages * C_IPP, q)
    eps_rows = rng.choice([2, 8], size=(2, q)).astype(np.int64)
    ch, th, cd, td = _occupancy_pair(positions, eps_rows, num_pages)
    assert cd.shape == (2, num_pages)
    scale = max(1.0, float(ch.max()))
    assert np.max(np.abs(ch - cd)) / scale < 2e-6
    assert np.max(np.abs(th - td) / np.maximum(th, 1.0)) < 2e-6


#: Pages past two page tiles of the padded histogram, so every band
#: crosses a tile edge somewhere and the last tile is ragged.
TILE = profile_grid._P_TILE
WIDE_PAGES = 2 * TILE + 104


def _edge_positions(rng, radius, q, slots):
    """Queries on page 0, on the last page and on both sides of each page
    tile edge (padded column = page + radius), the rest spread over the
    key file; ``slots(n)`` draws the in-page offsets."""
    edges = [e - radius + o for e in (TILE, 2 * TILE) for o in (-2, -1, 0, 1)]
    pages = np.concatenate([
        [0, 0, 1, WIDE_PAGES - 1, WIDE_PAGES - 1, WIDE_PAGES - 2],
        np.clip(edges, 0, WIDE_PAGES - 1),
        rng.integers(0, WIDE_PAGES, q)])
    return pages * C_IPP + slots(len(pages))


@pytest.mark.parametrize("radius", [1, 16, 32])
def test_occupancy_wide_band_integer_mass_exact(radius):
    """Bands of 3, 33 and 65 pages over several page tiles.  The first two
    rows keep eps <= 4 and slots >= 2*eps from both page edges, so their
    mass is integer and must match EXACTLY whatever the band's width; the
    last row alone carries the eps that sets the band (fractional mass,
    float32 tolerance)."""
    rng = np.random.default_rng(100 + radius)
    positions = _edge_positions(rng, radius, 1200,
                                lambda n: rng.integers(16, 112, n))
    q = len(positions)
    eps_rows = np.concatenate([
        rng.choice([1, 2, 4], size=(2, q)),
        np.full((1, q), radius * C_IPP // 2)]).astype(np.int64)
    assert page_ref.lut_radius(int(eps_rows.max()), C_IPP) == radius
    ch, th, cd, td = _occupancy_pair(positions, eps_rows, WIDE_PAGES)
    assert np.all(ch[:2] == np.round(ch[:2]))
    assert np.array_equal(ch[:2], cd[:2])
    assert np.array_equal(th[:2], td[:2])
    scale = max(1.0, float(ch[2].max()))
    assert np.max(np.abs(ch[2] - cd[2])) / scale < 2e-6


@pytest.mark.parametrize("radius", [1, 16, 32])
def test_occupancy_wide_band_general_tolerance(radius):
    """The same bands and tile edges with arbitrary slots and every pow2
    eps class up to the band's own: fractional mass in every band row,
    within the float32 tolerance of the host oracle."""
    rng = np.random.default_rng(200 + radius)
    positions = _edge_positions(rng, radius, 2500,
                                lambda n: rng.integers(0, C_IPP, n))
    q = len(positions)
    top = radius * C_IPP // 2
    classes = [1 << b for b in range(top.bit_length())]
    eps_rows = rng.choice(classes, size=(3, q)).astype(np.int64)
    eps_rows[:, 0] = top
    ch, th, cd, td = _occupancy_pair(positions, eps_rows, WIDE_PAGES)
    scale = max(1.0, float(ch.max()))
    assert np.max(np.abs(ch - cd)) / scale < 2e-6
    assert np.max(np.abs(th - td) / np.maximum(th, 1.0)) < 2e-6
