"""MemKV's sorted key list after new keys: a commit's few new keys are put
in place (no sort of every key, which held the store's lock for tenths of a
second at a million keys), a bulk of them is merged, and a key that garbage
collection removed rebuilds the list; every scan sees the keys in order."""

import random

import pytest

from tidb_tpu.store import kv as kv_mod
from tidb_tpu.store.kv import MemKV


def _key(rng) -> bytes:
    return b"t" + rng.randbytes(rng.randint(1, 12))


@pytest.fixture
def loaded():
    rng = random.Random(2147483659)
    kv = MemKV()
    for _ in range(3000):
        kv.put(_key(rng), b"v", 10)
    list(kv.scan(b"", b"\xff", 10))   # the first scan sorts every key
    return kv, rng


@pytest.fixture
def full_sorts(monkeypatch):
    """How many times every key of the store is sorted."""
    calls = []

    def counting(keys):
        calls.append(1)
        return sorted(keys)

    monkeypatch.setattr(kv_mod, "sorted", counting, raising=False)
    return calls


@pytest.mark.parametrize("new", [1, 2, kv_mod._INSORT_KEYS, kv_mod._INSORT_KEYS + 1, 2000])
def test_new_keys_join_the_order_without_a_full_sort(loaded, full_sorts, new):
    kv, rng = loaded
    for _ in range(new):
        kv.put(_key(rng), b"w", 11)
    kv.put(next(iter(kv._data)), b"again", 12)   # a new version of a known key adds no key
    got = [k for k, _ in kv.scan(b"", b"\xff", 12)]
    assert got == sorted(kv._data) and not kv._new
    assert full_sorts == []


def test_a_key_removed_by_gc_rebuilds_the_list(loaded, full_sorts):
    kv, rng = loaded
    gone = next(iter(kv._data))
    kv.put(gone, None, 20)   # a tombstone: gc at 20 drops the key
    kv.put(_key(rng), b"w", 20)
    assert kv.gc(20) >= 1 and gone not in kv._data
    got = [k for k, _ in kv.scan(b"", b"\xff", 30)]
    assert got == sorted(kv._data) and gone not in got
    assert full_sorts == [1]


def test_scan_between_puts_reads_each_range_in_order(loaded):
    kv, rng = loaded
    for i in range(50):
        kv.put(_key(rng), b"x", 40 + i)
        lo, hi = sorted((_key(rng), _key(rng)))
        want = sorted(k for k in kv._data if lo <= k < hi)
        assert [k for k, _ in kv.scan(lo, hi, 40 + i)] == want
