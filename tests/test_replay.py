"""The replay oracle's buffers (``core/replay.py``) against brute-force
references: the same hit/miss, the same victim and the same resident set
after every access of short random traces."""
import numpy as np
import pytest

from repro.core import replay


class _ListQueue:
    """Resident pages, oldest first; a miss evicts the front.  Under LRU a
    hit moves its page to the back; under FIFO it changes nothing."""

    def __init__(self, capacity, recency):
        self.capacity, self.recency, self.pages = capacity, recency, []

    def access(self, page):
        if page in self.pages:
            if self.recency:
                self.pages.remove(page)
                self.pages.append(page)
            return True, None
        victim = None
        if len(self.pages) == self.capacity:
            victim = self.pages.pop(0)
        self.pages.append(page)
        return False, victim


class _ScanLFU:
    """Evicts the resident page of least (count, time of last access): the
    least frequent, and of those the least recently used.  A page's count
    restarts at 1 when it re-enters."""

    def __init__(self, capacity):
        self.capacity, self.count, self.last, self.now = capacity, {}, {}, 0

    def access(self, page):
        self.now += 1
        hit = page in self.count
        victim = None
        if hit:
            self.count[page] += 1
        else:
            if len(self.count) == self.capacity:
                victim = min(self.count,
                             key=lambda p: (self.count[p], self.last[p]))
                del self.count[victim], self.last[victim]
            self.count[page] = 1
        self.last[page] = self.now
        return hit, victim

    @property
    def pages(self):
        return list(self.count)


class _SecondChance:
    """CLOCK as a second-chance queue: the front page with its bit set goes
    to the back with the bit cleared; the first with it clear is evicted.
    A new page enters at the back with its bit set."""

    def __init__(self, capacity):
        self.capacity, self.queue, self.bit = capacity, [], {}

    def access(self, page):
        if page in self.bit:
            self.bit[page] = 1
            return True, None
        victim = None
        if len(self.queue) == self.capacity:
            while self.bit[self.queue[0]]:
                front = self.queue.pop(0)
                self.bit[front] = 0
                self.queue.append(front)
            victim = self.queue.pop(0)
            del self.bit[victim]
        self.queue.append(page)
        self.bit[page] = 1
        return False, victim

    @property
    def pages(self):
        return list(self.queue)


REFERENCES = {"lru": lambda c: _ListQueue(c, recency=True),
              "fifo": lambda c: _ListQueue(c, recency=False),
              "lfu": _ScanLFU, "clock": _SecondChance}


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("policy", ["lru", "fifo", "lfu", "clock"])
def test_buffer_matches_brute_force(policy, seed):
    """Skewed references over 40 pages through a 7-page buffer: hits,
    evictions and re-entries all occur."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, 41) ** 0.8
    trace = rng.choice(40, size=400, p=p / p.sum())
    buf = replay.make_buffer(policy, 7)
    ref = REFERENCES[policy](7)
    hits = 0
    for i, page in enumerate(trace.tolist()):
        hit, victim = ref.access(page)
        assert buf.access(page) is hit, (policy, seed, i)
        assert buf.last_evicted == victim, (policy, seed, i)
        assert all(q in buf for q in ref.pages), (policy, seed, i)
        hits += hit
    assert len(ref.pages) == 7
    assert 0 < hits < len(trace)
    assert replay.replay_refs(trace, 7, policy) == (hits, len(trace) - hits)
