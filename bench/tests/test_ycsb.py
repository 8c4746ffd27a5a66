"""The YCSB-A generator (``ycsb.py``): a function of the seed, 50/50 reads
and updates within binomial noise, zipfian ranks at constant 0.99, and
YCSB's FNV-1a scatter."""
import numpy as np
import pytest

import run
import ycsb


@pytest.fixture
def mix():
    return dict(run.load_json(run.HERE, "traffic", "ycsb_a.json"),
                batch_events=2_000, batches=8)


def test_pool_is_a_function_of_the_seed(mix):
    a = ycsb.make_pool(mix, 50_000, 2**31 + 11)
    b = ycsb.make_pool(mix, 50_000, 2**31 + 11)
    c = ycsb.make_pool(mix, 50_000, 12)
    assert all(np.array_equal(x.reads, y.reads)
               and np.array_equal(x.positions, y.positions)
               for x, y in zip(a, b))
    assert not all(np.array_equal(x.positions, y.positions)
                   for x, y in zip(a, c))
    # every seed gives the same count and size of batches
    assert [x.reads.shape for x in a] == [x.reads.shape for x in c]
    assert all(0 <= x.positions.min() and x.positions.max() < 50_000
               for x in a)
    assert np.array_equal(ycsb.osm_keys(10_000, 3), ycsb.osm_keys(10_000, 3))
    keys = ycsb.osm_keys(10_000, 3)
    assert keys.dtype == np.uint64 and np.all(np.diff(keys) > 0)


def test_reads_and_updates_split_half_and_half(mix):
    reads = np.concatenate([b.reads for b in
                            ycsb.make_pool(mix, 50_000, 2**32 + 3)])
    n = reads.shape[0]
    # within four binomial standard deviations of n / 2
    assert abs(int(reads.sum()) - n / 2) < 4 * np.sqrt(n / 4)


def test_ranks_follow_zipf_0_99():
    n, size = 1_000, 2_000_000
    ranks = ycsb.zipf_ranks(n, size, 0.99, np.random.default_rng(5))
    freq = np.bincount(ranks, minlength=n) / size
    # the two head ranks take the exact zipfian mass
    zetan = ycsb.zeta(n, 0.99)
    assert freq[0] == pytest.approx(1 / zetan, rel=0.01)
    assert freq[1] == pytest.approx(2 ** -0.99 / zetan, rel=0.02)
    # rank frequency over ranks 10..500 falls with slope -0.99 (log-log)
    r = np.arange(10, 500)
    slope = np.polyfit(np.log(r + 1), np.log(freq[r]), 1)[0]
    assert slope == pytest.approx(-0.99, abs=0.05)


def test_fnv_scatter_is_ycsbs_fnvhash64():
    def fnvhash64(val):                 # YCSB's Utils.fnvhash64, in Python
        h = 0xCBF29CE484222325
        for _ in range(8):
            h ^= val & 0xFF
            val >>= 8
            h = (h * 1099511628211) % 2**64
        signed = h - 2**64 if h >= 2**63 else h
        return abs(signed)
    vals = np.asarray([0, 1, 2, 255, 256, 99_999, 2**40 + 7])
    assert ycsb.fnv1a_64(vals).tolist() == [fnvhash64(int(v)) for v in vals]
    pos = ycsb.scrambled_zipfian(1_000, 10_000, 0.99,
                                 np.random.default_rng(1))
    assert pos.min() >= 0 and pos.max() < 1_000
